"""What the cells read is pinned: the weights, the reference's gaps, the
FLOP counts and the kernel costs, at smoke sizes on the CPU, equal exactly
the numbers the harness gave before it was split into family modules
(seed 5, fixed tokens). A change to any of them moves every cell that
reads it, and belongs to a benchmark change of its own."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import smoke
import test_profile

from soibench import costs, families, model, profile, reference
from soibench.families import dense_gqa

# sha256 (first 16 hex digits) of each weight leaf's bytes, seed 5; the fp
# file differs from the pp one only in the SOI mode, which no leaf follows
QWEN3_LEAVES = {
    "embed": "84422008bbe2c53a",
    "final_norm/scale": "da9777d7b7b10f5a",
    "segments/0/sub0/attn/k_norm/scale": "dc2e2d39c14d9072",
    "segments/0/sub0/attn/q_norm/scale": "d34b05115ee58009",
    "segments/0/sub0/attn/wk": "e4791a94f6e418d6",
    "segments/0/sub0/attn/wo": "11429bb6472ca9a8",
    "segments/0/sub0/attn/wq": "99b2ad3bd2dbe888",
    "segments/0/sub0/attn/wv": "a9e717f84bb1eedc",
    "segments/0/sub0/ln1/scale": "e5eaf31eddcd8901",
    "segments/0/sub0/ln2/scale": "931cfd6089d689c8",
    "segments/0/sub0/mlp/down": "fe56a9c4fb736499",
    "segments/0/sub0/mlp/gate": "e28c13edd67077ba",
    "segments/0/sub0/mlp/up": "5ef6412d70cd783a",
    "soi/compress": "52a310773f2d36e2",
    "soi/fuse": "b7a0e156a7e47506",
}
LEAVES = {
    "qwen3-1.7b-soi-pp": QWEN3_LEAVES,
    "qwen3-1.7b-soi-fp": QWEN3_LEAVES,
    "h2o-danube-1.8b-soi-pp": {
        "embed": "01e7744e6d3d0e05",
        "final_norm/scale": "ef993abd901edc9d",
        "lm_head": "e41f2177c2d42575",
        "segments/0/sub0/attn/wk": "e4791a94f6e418d6",
        "segments/0/sub0/attn/wo": "11429bb6472ca9a8",
        "segments/0/sub0/attn/wq": "99b2ad3bd2dbe888",
        "segments/0/sub0/attn/wv": "a9e717f84bb1eedc",
        "segments/0/sub0/ln1/scale": "e5eaf31eddcd8901",
        "segments/0/sub0/ln2/scale": "931cfd6089d689c8",
        "segments/0/sub0/mlp/down": "3045047002b22a24",
        "segments/0/sub0/mlp/gate": "ba89ab7c197eea5d",
        "segments/0/sub0/mlp/up": "f9234136390c63c0",
        "soi/compress": "52a310773f2d36e2",
        "soi/fuse": "b7a0e156a7e47506",
    },
}

# reference.gaps of 64 fixed tokens against 64 fixed targets (float32)
GAPS = {
    "qwen3-1.7b-soi-pp": [
        11.457617, 13.609332, 10.417467, 23.804092, 10.866913,
        14.244114, 13.12735, 4.8268805, 13.873071, 7.515052, 10.575304,
        10.8021965, 19.098547, 14.331918, 9.124572, 15.500282,
        18.703709, 16.421616, 12.258175, 7.059459, 8.173244, 8.975571,
        11.778358, 11.639577, 13.572285, 11.325424, 14.634347,
        12.1809025, 12.370206, 13.802349, 6.910849, 11.093671,
        12.747669, 16.72576, 11.197401, 10.352583, 13.615568,
        10.438841, 11.922163, 20.481747, 13.127585, 10.913297, 7.65254,
        2.064825, 11.474533, 16.82902, 5.3570943, 18.761929, 11.524132,
        7.00987, 17.611725, 8.558626, 7.5774713, 12.334431, 0.0,
        17.306257, 5.112443, 16.68838, 12.173634, 17.7133, 12.490518,
        15.5822935, 4.2368526, 10.986385,
    ],
    "qwen3-1.7b-soi-fp": [
        11.494197, 13.077054, 9.163215, 23.230381, 10.276609, 13.81521,
        14.8846245, 3.9176788, 13.195717, 7.954232, 8.4885845,
        10.466425, 18.365158, 14.037442, 8.173565, 16.09289, 16.61766,
        16.50477, 8.978021, 6.308169, 14.312953, 8.985742, 14.164822,
        11.622086, 11.172778, 11.316305, 19.047207, 12.118061,
        10.129853, 13.900148, 6.6789927, 10.952676, 8.76816, 16.777796,
        12.626692, 10.263527, 7.646347, 10.873998, 11.728173,
        20.738338, 13.923489, 10.752914, 8.638403, 1.7726312, 8.734614,
        16.769205, 6.0366116, 18.5171, 9.3348, 7.3749666, 16.715899,
        8.707072, 3.2906513, 12.533075, 0.0, 17.618696, 7.183956,
        16.861177, 17.621094, 18.027748, 11.708494, 16.070938,
        9.432978, 10.893865,
    ],
    "h2o-danube-1.8b-soi-pp": [
        12.952723, 12.782407, 14.900633, 11.757106, 6.390071,
        12.678285, 15.232529, 9.702962, 15.035972, 7.7501907,
        1.4431019, 8.960586, 12.164194, 17.920835, 11.320113,
        7.3913107, 7.9214597, 4.2316203, 6.7579594, 10.96956,
        7.8022404, 13.227638, 17.185698, 14.410252, 7.7693205,
        13.174562, 16.080414, 12.408152, 18.835526, 14.475435,
        16.10256, 6.4767094, 12.6307125, 17.33148, 7.454867, 9.47617,
        13.259017, 3.8432488, 8.400224, 14.980167, 11.489977,
        19.730328, 13.101899, 10.700143, 11.950614, 11.746708,
        8.301385, 13.267815, 10.531885, 5.953082, 11.989406, 8.958645,
        13.203625, 14.478457, 16.47465, 15.89514, 6.712403, 9.200315,
        9.628359, 8.00545, 9.328054, 10.150223, 6.6712713, 7.927055,
    ],
}

# token_flops at positions 0, 15 and 99: (with, without) the LM head
FLOPS = {
    "qwen3-1.7b-soi-pp": {
        0: (353024.0, 320256.0),
        15: (362496.0, 329728.0),
        99: (416256.0, 383488.0),
    },
    "qwen3-1.7b-soi-fp": {
        0: (353024.0, 320256.0),
        15: (362496.0, 329728.0),
        99: (416256.0, 383488.0),
    },
    "h2o-danube-1.8b-soi-pp": {
        0: (316160.0, 283392.0),
        15: (321536.0, 288768.0),
        99: (321536.0, 288768.0),
    },
}

# each kernel's cost on the chat cell's shapes (test_profile)
COSTS = {
    "paged_decode_attention": {"flops": 268435456.0, "bytes": 134488128.0},
    "chunk_attention": {"flops": 4294967296.0, "bytes": 10494976.0},
    "copy_pages": {"flops": 0.0, "bytes": 1048704.0},
}

# the chat cell's chunk_attention (chunk 256 of a 2048 context) and
# copy_pages (16 page copies) calls, as the trace gives their operands
CHUNK = (costs.Shape((1, 256, 16, 128), 2),
         [costs.Shape((1, 256, 16, 128), 2), costs.Shape((1, 2048, 8, 128), 2),
          costs.Shape((1, 2048, 8, 128), 2), costs.Shape((1, 256), 4),
          costs.Shape((1, 2048), 4)])
COPY = (costs.Shape((2049, 16, 8, 128), 2),
        [costs.Shape((2, 16), 4), costs.Shape((2049, 16, 8, 128), 2)])


def _path(keys) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in keys)


@pytest.mark.parametrize("name", list(LEAVES))
def test_weights_are_pinned(name):
    w = model.make_weights(smoke.config(name), model.seed_key(5, 0))
    got = {_path(p): hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
           for p, x in jax.tree_util.tree_flatten_with_path(w)[0]}
    assert got == LEAVES[name]


@pytest.mark.parametrize("name", list(GAPS))
def test_reference_gaps_are_pinned(name):
    c = smoke.config(name)
    fam = families.of(c)
    s = fam.sizes(c)
    w = model.make_weights(c, model.seed_key(5, 0))
    n = reference.padded_len(64, s["stride"])
    rng = np.random.default_rng(64)
    tokens, targets = np.zeros(n, np.int32), np.zeros(n, np.int32)
    tokens[:64] = rng.integers(0, s["vocab"], 64)
    targets[:64] = rng.integers(0, s["vocab"], 64)
    g = reference.gaps(fam, w, reference.frozen(s), jnp.asarray(tokens),
                       jnp.asarray(targets))
    np.testing.assert_array_equal(np.asarray(g)[:64],
                                  np.asarray(GAPS[name], np.float32))


@pytest.mark.parametrize("name", list(FLOPS))
def test_token_flops_are_pinned(name):
    c = smoke.config(name)
    fam = families.of(c)
    s = fam.sizes(c)
    assert {p: (fam.token_flops(s, p, True), fam.token_flops(s, p, False))
            for p in (0, 15, 99)} == FLOPS[name]


def test_kernel_costs_are_pinned():
    paged = profile.hlo_shapes(test_profile.PAGED)
    table = families.kernel_costs(dense_gqa)
    for kernel, (out, ops) in [("paged_decode_attention", paged),
                               ("chunk_attention", CHUNK),
                               ("copy_pages", COPY)]:
        assert table[kernel] is costs.KERNELS[kernel]
        assert table[kernel](out, ops) == COSTS[kernel]
