"""The fused prefill attention kernel (ops/pallas/attention.py) under the
Pallas interpreter on the CPU, at toy sizes, held to the XLA path of
`ops/attention.py:blockwise_attention`; the rule that sends a call to it
(`runs_fused`) and the counter of the q blocks it ran, through the toy
generate programs of both decoder families. (The reference repository has no
attention: no analogue.)"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_decoder as toy  # noqa: E402
import test_gqa_decoder as toy_gqa  # noqa: E402

from benchmark import decoder_check  # noqa: E402
from real_time_helmet_detection_tpu.models import decoder as dec  # noqa: E402
from real_time_helmet_detection_tpu.ops import attention as att  # noqa: E402
from real_time_helmet_detection_tpu.ops.pallas import (  # noqa: E402
    attention as fused)

Q_BLOCK, TOTAL, HEADS = 8, 32, 4
SCALE = 0.3
CASES = ("plain", "chosen", "dq_ne_dv", "shared", "own_key_only")


def _operands(case, dtype=jnp.float32):
    rng = np.random.default_rng(11)
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), dtype)
    dq, dv = (24, 8) if case == "dq_ne_dv" else (16, 16)
    q, k, v = (draw(HEADS, TOTAL, dq), draw(HEADS, TOTAL, dq),
               draw(HEADS, TOTAL, dv))
    shared = (draw(HEADS, TOTAL, 4), draw(TOTAL, 4)) \
        if case == "shared" else None
    return q, k, v, shared, (draw(TOTAL, 2, 16), draw(TOTAL, 16),
                             draw(TOTAL, 2))


def _chosen(case, index, length):
    """The list `select_blocks` gives (top 6 of the causal keys), every row's
    own key alone, or no list."""
    if case == "own_key_only":
        return [jnp.arange(r0, r0 + Q_BLOCK)[:, None]
                == jnp.arange(r0 + Q_BLOCK)[None, :]
                for r0 in range(0, TOTAL, Q_BLOCK)]
    if case == "plain":
        return None
    return att.select_blocks(*index, 6, Q_BLOCK, length=length)


def _mask(chosen):
    return None if chosen is None else att.chosen_mask(chosen, TOTAL)


def _both(case, length, dtype=jnp.float32, **tiles):
    """(the kernel's answer, the XLA path's) for one sequence."""
    q, k, v, shared, index = _operands(case, dtype)
    length = jnp.int32(length)
    chosen = _chosen(case, index, length)
    got = fused.attn_fused(q, k, v, length, _mask(chosen), *(shared or ()),
                           q_block=Q_BLOCK, scale=SCALE, interpret=True,
                           **tiles)
    assert not att.runs_fused(3, TOTAL, Q_BLOCK, None)  # the CPU: XLA's
    want = att.blockwise_attention(q, k, v, q_block=Q_BLOCK, scale=SCALE,
                                   length=length, chosen=chosen,
                                   shared=shared, head_block=2)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


@pytest.mark.parametrize("length", [1, 11, 2 * Q_BLOCK, TOTAL])
@pytest.mark.parametrize("case", CASES)
def test_the_kernel_equals_the_xla_path(case, length):
    """To float32 rounding, over every row of a block that ran; the rows of
    a q block that starts at or past `length` are zeros; rows below `length`
    are what the full length gives; no NaN."""
    got, want = _both(case, length)
    full, _ = _both(case, TOTAL)
    ran = -(-length // Q_BLOCK) * Q_BLOCK
    assert got.shape == want.shape and not np.isnan(got).any()
    assert np.allclose(got[:, :ran], want[:, :ran], atol=2e-6)
    assert not got[:, ran:].any() and not want[:, ran:].any()
    assert np.allclose(got[:, :length], full[:, :length], atol=2e-6)
    assert full[:, length:].any() or length == TOTAL


def test_a_row_whose_only_key_is_its_own_answers_that_value():
    got, _ = _both("own_key_only", TOTAL)
    assert np.allclose(got, np.asarray(_operands("own_key_only")[2]),
                       atol=1e-6)


@pytest.mark.parametrize("tiles", [(4, 1), (8, 2), (16, 4), (32, 4)],
                         ids=lambda t: "k%d_h%d" % t)
def test_the_tiling_changes_no_answer(tiles):
    """Key blocks smaller than, equal to and larger than the q block (the
    diagonal crosses a tile; a tile holds keys past the q block's end), one
    head or all of them a grid step."""
    got, want = _both("shared", 19, k_block=tiles[0], head_tile=tiles[1])
    assert np.allclose(got[:, :24], want[:, :24], atol=2e-6)
    assert not got[:, 24:].any()


def test_bfloat16_operands_stay_within_bfloat16_of_the_xla_path():
    """`p` is cast to the values' dtype under a running maximum here and
    under the row's whole maximum there: two roundings of one number."""
    got, want = _both("chosen", TOTAL, jnp.bfloat16)
    exact, _ = _both("chosen", TOTAL)
    assert np.allclose(got, want, atol=4e-2)
    assert np.abs(got - exact).max() < 1.5 * np.abs(want - exact).max() + 1e-2


def test_rows_of_different_lengths_under_lax_map():
    q, k, v, shared, index = _operands("shared")
    lengths = jnp.asarray([TOTAL, 3, 17, Q_BLOCK], jnp.int32)
    shift = jnp.arange(4, dtype=jnp.float32)[:, None, None, None] * 0.1

    def row(kernel):
        def one(xs):
            bias, n = xs
            chosen = att.select_blocks(*index, 6, Q_BLOCK, length=n)
            if kernel:
                return fused.attn_fused(
                    q + bias, k, v, n, _mask(chosen), *shared,
                    q_block=Q_BLOCK, scale=SCALE, interpret=True)
            return att.blockwise_attention(
                q + bias, k, v, q_block=Q_BLOCK, scale=SCALE, length=n,
                chosen=chosen, shared=shared)
        return jax.jit(lambda: jax.lax.map(one, (shift, lengths)))()
    got, want = np.asarray(row(True)), np.asarray(row(False))
    assert np.allclose(got, want, atol=2e-6)
    for r, n in enumerate(np.asarray(lengths)):
        assert not got[r][:, -(-n // Q_BLOCK) * Q_BLOCK:].any()
    assert np.abs(got[0] - got[2]).max() > 1e-3


def _lowered(monkeypatch, **how):
    monkeypatch.setattr(att, "kernel_compiles", lambda: True)
    q, k, v, _, _ = _operands("plain")
    if how.pop("grouped", False):
        q = q.reshape(2, 2, TOTAL, -1)
        k, v = k[:2], v[:2]
    return jax.jit(lambda n: att.blockwise_attention(
        q, k, v, q_block=Q_BLOCK, scale=SCALE, length=n, **how)
    ).lower(jnp.int32(TOTAL)).as_text(debug_info=True)


def test_the_rule_a_per_head_call_without_a_window_is_the_kernel(
        monkeypatch):
    """Where Mosaic compiles (here: said to, so the interpreter runs it).
    A windowed call and a grouped call keep the XLA path; so does every call
    where it does not compile, and rows that are not whole q blocks."""
    assert "attn_fused" in _lowered(monkeypatch)
    assert "attn_fused" not in _lowered(monkeypatch, window=5)
    assert "attn_fused" not in _lowered(monkeypatch, grouped=True)
    assert att.runs_fused(3, TOTAL, Q_BLOCK, None)
    assert not att.runs_fused(3, TOTAL + 1, Q_BLOCK, None)
    monkeypatch.undo()
    assert not att.runs_fused(3, TOTAL, Q_BLOCK, None)
    with pytest.raises(ValueError, match="whole q blocks"):
        fused.attn_fused(*_operands("plain")[:3], jnp.int32(3), q_block=5,
                         scale=SCALE, interpret=True)


@pytest.fixture(scope="module")
def fields():
    return toy.bench_toy.toy_fields("dots3-note-prev-ep8-l5")


def _counts(served, spec):
    return ([int(s.q_blocks_fused) for s in served],
            [int(s.q_blocks_run) * spec.full_layers // spec.layers
             for s in served])


def test_the_toy_program_with_the_kernel_forced_is_within_the_cells_limits(
        fields, monkeypatch):
    """Prefill and 12 steps of the batch of mixed lengths, the two full
    layers through the kernel (the interpreter), against the reference: the
    cell's own limits; and `q_blocks_fused` counts the full layers' blocks,
    and none where the XLA path ran."""
    spec = dec.DecoderSpec.from_mapping(toy._config(fields).decoder)
    rows, plain = toy._generate(fields)
    fused_n, want_n = _counts(plain, spec)
    assert fused_n == [0] * len(plain) and min(want_n) > 0
    monkeypatch.setattr(att, "kernel_compiles", lambda: True)
    rows, served = toy._generate(fields)
    fused_n, want_n = _counts(served, spec)
    assert fused_n == want_n
    wants = decoder_check.reference_answers(fields, toy.SEED, list(rows),
                                            served)
    numbers = decoder_check.numbers(list(rows), served, wants)
    with open(os.path.join(toy.bench_toy.REPO, "benchmark", "workloads",
                           "gen-8k-64.json")) as f:
        limits = toy.json.load(f)["toy"]["limits"]
    for name, limit in limits.items():
        assert numbers[name] <= limit, (name, numbers)
    for s, p in zip(served, plain):
        assert int(s.keys_kept) == int(p.keys_kept)
        assert int(s.q_blocks_run) == int(p.q_blocks_run)


def test_the_kernel_forced_in_float32_matches_the_full_forward(
        fields, monkeypatch):
    monkeypatch.setattr(att, "kernel_compiles", lambda: True)
    rows, served = toy._generate(fields, jnp.float32)
    wants = decoder_check.reference_answers(fields, toy.SEED, list(rows),
                                            served)
    for s, w in zip(served, wants):
        assert np.allclose(s.logits_first, w["logits"][0], atol=2e-5)
        assert np.allclose(s.logits_last, w["logits"][-1], atol=2e-5)
        assert np.array_equal(s.tokens, np.argmax(w["logits"], -1))
        assert int(s.keys_kept) == int(w["keys_kept"])
        assert int(s.q_blocks_fused) > 0


def test_the_grouped_family_never_takes_the_kernel(monkeypatch):
    """Every call of the grouped-query family is the grouped form: its
    program holds no `attn_fused` and counts no fused block, even where the
    kernel would compile."""
    monkeypatch.setattr(att, "kernel_compiles", lambda: True)
    fields = toy_gqa.bench_toy.toy_fields("laguna-xs2-l5")
    _, served = toy_gqa._generate(fields)
    assert [int(s.q_blocks_fused) for s in served] == [0] * len(served)
    assert min(int(s.q_blocks_run) for s in served) > 0
