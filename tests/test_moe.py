"""The expert layer's pieces (ops/moe.py, ops/pallas/expert_gmm.py,
parallel/experts.py) at toy sizes on the CPU: the router, the share, the
sorted grouped matmul against a dense computation, the passes when routing
is skewed past the capacity. (The reference has no experts: no analogue.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from real_time_helmet_detection_tpu.ops import moe
from real_time_helmet_detection_tpu.ops.pallas import expert_gmm as gmm
from real_time_helmet_detection_tpu.parallel.experts import expert_share


def _layer(seed, tokens, hidden=32, width=16, experts=8, bias=0.1):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    return dict(hn=f(tokens, hidden), w_router=0.3 * f(hidden, experts),
                b=bias * f(experts), w_gate_up=0.2 * f(experts, hidden,
                                                       2 * width),
                w_down=0.2 * f(experts, width, hidden))


def _dense(p, idx, weights, real):
    """Every expert over every token, weighted by the routing: the plain
    computation the sorted one must equal."""
    total = 0.0
    for e in range(p["w_router"].shape[1]):
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        total = total + moe.swiglu(p["hn"], p["w_gate_up"][e],
                                   p["w_down"][e]) * w_e[:, None]
    return total * real[:, None]


def test_route_chooses_by_biased_score_and_weighs_by_the_unbiased():
    p = _layer(0, 24)
    idx, w = moe.route(p["hn"], p["w_router"], p["b"], 2, True, 2.5)
    s = np.asarray(jax.nn.sigmoid(p["hn"] @ p["w_router"]))
    want = np.argsort(-(s + np.asarray(p["b"])), axis=-1)[:, :2]
    assert np.array_equal(np.sort(idx, -1), np.sort(want, -1))
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    assert np.allclose(w, 2.5 * picked / picked.sum(-1, keepdims=True),
                       atol=1e-6)
    plain, _ = moe.route(p["hn"], p["w_router"], 0 * p["b"], 2, True, 2.5)
    assert not np.array_equal(np.sort(idx, -1), np.sort(plain, -1))


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged_dot", "pallas_interpret"])
def test_the_shares_add_up_to_the_dense_layer(interpret):
    p = _layer(1, 40)
    idx, w = moe.route(p["hn"], p["w_router"], p["b"], 2, True, 1.0)
    real = jnp.arange(40) < 33
    total = 0.0
    for rank in range(2):
        sh = expert_share(2, rank, 8)
        held = slice(sh.first, sh.first + sh.held)
        y, local = moe.routed_experts(p["hn"], idx, w, real,
                                      p["w_gate_up"][held], p["w_down"][held],
                                      sh, interpret)
        here = (np.asarray(idx) >= sh.first) \
            & (np.asarray(idx) < sh.first + sh.held) & np.asarray(real)[:, None]
        assert np.array_equal(np.asarray(local) < sh.held, here)
        total = total + y
    assert np.allclose(total, _dense(p, idx, w, real), atol=2e-5)
    assert not np.any(np.asarray(total)[33:])  # padding takes no expert


def test_routing_skewed_past_the_capacity_takes_more_passes_and_drops_none():
    p = _layer(2, 1024, experts=16)
    sh = expert_share(8, 0, 16)            # 2 of 16 experts held
    # the selection bias sends nearly every token to the two held experts
    b = p["b"].at[:2].add(5.0)
    idx, w = moe.route(p["hn"], p["w_router"], b, 2, True, 1.0)
    real = jnp.ones((1024,), bool)
    assert moe.capacity_rows(2048, sh) == 512
    y, local = moe.routed_experts(p["hn"], idx, w, real, p["w_gate_up"][:2],
                                  p["w_down"][:2], sh)
    assert int(np.sum(np.asarray(local) < 2)) > 3 * 512
    want = _dense(dict(p, w_router=p["w_router"][:, :2]), idx, w, real)
    assert np.allclose(y, want, atol=5e-5)


@pytest.mark.parametrize("pairs,share,rows", [
    (32, (8, 0, 256), 32),           # a decode step: every pair, 16-aligned
    (36, (8, 0, 256), 48),
    (262144, (8, 0, 256), 65536),    # the cell's prefill: twice the even load
    (2048, (1, 0, 8), 2048),         # one chip holds all: never over the pairs
])
def test_capacity_rows(pairs, share, rows):
    assert moe.capacity_rows(pairs, expert_share(*share)) == rows


@pytest.mark.parametrize("sizes", [[10, 0, 23, 7, 4], [0, 0, 0, 0, 40],
                                   [64, 0, 0, 0, 0], [0, 0, 0, 0, 0]])
def test_expert_gmm_kernel_matches_ragged_dot(sizes):
    rng = np.random.default_rng(3)
    lhs = jnp.asarray(rng.standard_normal((64, 256)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((5, 256, 384)), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    tiling = (16, 128, 128)
    meta = gmm.group_metadata(sizes, 64, gmm.row_tile(64, tiling))
    out = gmm.expert_gmm(lhs, rhs, meta, tiling=tiling, interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    n = int(sizes.sum())
    assert np.allclose(np.asarray(out)[:n], np.asarray(want)[:n], atol=1e-3)


@pytest.mark.parametrize("args", [(3, 0, 8), (2, 2, 8), (0, 0, 8)])
def test_a_share_that_does_not_divide_the_experts_is_refused(args):
    with pytest.raises(ValueError):
        expert_share(*args)


def test_a_share_names_the_ids_it_holds():
    assert list(expert_share(8, 3, 256).ids()) == list(range(96, 128))
