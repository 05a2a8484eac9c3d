"""Configuration files and cells cut to the program's smoke sizes."""

import copy
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]

# the program's smoke_config sizes of each architecture
SMOKE = {
    "qwen3-1.7b": {"hidden_size": 64, "intermediate_size": 192,
                   "num_hidden_layers": 4, "num_attention_heads": 4,
                   "num_key_value_heads": 2, "head_dim": 16,
                   "vocab_size": 256},
    "h2o-danube-1.8b": {"hidden_size": 64, "intermediate_size": 160,
                        "num_hidden_layers": 4, "num_attention_heads": 4,
                        "num_key_value_heads": 2, "head_dim": 16,
                        "vocab_size": 256, "sliding_window": 8},
}


def config(name: str) -> dict:
    """``bench/configs/<name>.json`` at the program's smoke sizes, computed
    in float32 so that a sound run reads as the reference does."""
    with open(BENCH / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    cfg.update(SMOKE[cfg["program"]["arch"]], compute_dtype="float32")
    n = cfg["num_hidden_layers"]
    cfg["soi"] = dict(cfg["soi"], first_layer=n // 4,
                      last_layer=n - n // 4)
    cfg["program"] = dict(cfg["program"], smoke=True)
    return cfg


def mix(name: str, **engine) -> dict:
    """``bench/traffic/<name>.json`` cut to smoke lengths."""
    with open(BENCH / "traffic" / f"{name}.json") as f:
        m = copy.deepcopy(json.load(f))
    m["engine"].update(engine)
    return m

# cells at smoke lengths: what each mix does, at sizes the CPU runs in
# seconds (page 4, chunk 8; the prefix is one cache-aligned block of 32)
SMOKE_MIX = {
    "chat-closed": {"clients": 6, "pool": 16,
                    "prompt": {"lo": 8, "hi": 48},
                    "output": {"lo": 8, "hi": 32}, "sample": 2,
                    "engine": {"slots": 4, "max_len": 96, "page_size": 4,
                               "chunk": 8}},
    "long-closed": {"clients": 4, "pool": 8,
                    "prompt": {"lo": 64, "hi": 96},
                    "output": {"lo": 4, "hi": 16}, "sample": 2,
                    "engine": {"slots": 3, "max_len": 112, "page_size": 4,
                               "chunk": 8}},
    "rag-open": {"rate_hz": 4.0, "pool": 16,
                 "prefix": {"tenants": 3, "len": 32, "zipf_a": 1.1},
                 "prompt": {"lo": 4, "hi": 16},
                 "output": {"lo": 8, "hi": 24}, "sample": 3, "grace_s": 30,
                 "engine": {"slots": 3, "max_len": 80, "page_size": 4,
                            "chunk": 8}},
}


def smoke_mix(name: str) -> dict:
    m = mix(name)
    cut = copy.deepcopy(SMOKE_MIX[name])
    m["engine"].update(cut.pop("engine"))
    m.update(cut)
    return m


# an open-loop cell that BENCHMARK.json does not list (yet): the harness's
# open loop and prefix-cache warm-up are tested through it
EXTRA = {"qwen3-rag-open": {"name": "qwen3-rag-open",
                            "config": "qwen3-1.7b-soi-pp",
                            "traffic": "rag-open", "chips": 1}}


def cell(workload: str, limit=1e9):
    """The workload of BENCHMARK.json (or of ``EXTRA``) at smoke sizes."""
    from soibench import spec
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    w = {x["name"]: x for x in bench["workloads"]}.get(workload) \
        or EXTRA[workload]
    app = [m for m in bench["end_to_end"] + bench["per_layer"]
           if "workloads" not in m or workload in m["workloads"]]
    if workload in EXTRA:
        app.append({"name": "ttft_p95_ms", "unit": "ms"})
        bench["end_to_end"].append(app[-1])
    return spec.Cell(
        name=workload, config_name=w["config"], traffic_name=w["traffic"],
        chips=w["chips"], config=config(w["config"]),
        traffic=smoke_mix(w["traffic"]), limits={"max_gap": limit},
        end_to_end=tuple(m for m in app if m in bench["end_to_end"]),
        per_layer=tuple(m for m in app if m in bench["per_layer"]))


class FakeChip:
    """Stands in for the device that ``run.chip`` would return."""
    platform = "cpu"
    device_kind = "cpu"

    def memory_stats(self):
        return {}
