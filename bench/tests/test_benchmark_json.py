"""BENCHMARK.json names only what the harness can find and run."""

import json
import re

import pytest
import smoke

from soibench import spec

BENCH = json.loads((smoke.BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# a configuration never cuts a width: every key ending in _size, _dim or
# _rank (hidden_size, moe_intermediate_size, head_dim, kv_lora_rank, ...),
# an expansion factor or the experts per token. Counts may be the chip's
# share: num_hidden_layers and n_routed_experts, and of the _size keys
# vocab_size alone, by name, so that a width nobody listed stays forbidden.
COUNTS = {"vocab_size"}
WIDTHS = {"num_experts_per_tok", "mlp_ratio", "expand"}


def is_width(key: str) -> bool:
    return key in WIDTHS or (key.endswith(("_size", "_dim", "_rank"))
                             and key not in COUNTS)


def test_names_units_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCH["per_layer"]:
        assert 0 < len(m["layer"]) <= 200
        # each names its cells, so that a cell added later brings its own
        assert m["workloads"], m["name"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_cell_has_its_files_and_its_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        cell.family.program_config(cell.config)
        assert cell.limits["max_gap"] is not None
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in reported, (w["name"], m["name"])
        for m in cell.end_to_end + cell.per_layer:
            assert (smoke.BENCH / "metrics" / f"{m['name']}.py").is_file()
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e


def test_every_config_is_used_and_keeps_its_widths():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        for key in c["reduced"]:
            assert not is_width(key), (c["name"], key)


@pytest.mark.parametrize("key,width", [
    ("hidden_size", True), ("shared_expert_intermediate_size", True),
    ("head_dim", True), ("qk_rope_head_dim", True), ("kv_lora_rank", True),
    ("num_experts_per_tok", True), ("vocab_size", False),
    ("num_hidden_layers", False), ("n_routed_experts", False)])
def test_widths_are_told_from_counts(key, width):
    assert is_width(key) is width
