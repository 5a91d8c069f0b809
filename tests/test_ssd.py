"""`ops/ssd.py ssd_scan` (ISSUE 39): the chunked selective state-space scan
against the recurrence it regroups, position by position in float64, for
histories that are whole chunks, not, and shorter than one; the state
carried across chunks and never across histories; padding; the ``read``
form; grouping; bfloat16 operands; what the lowered program holds. And
`ops/short_conv.py causal_conv`, the plain convolution in front of it. All
on the CPU, seeded inputs, small shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlops_tpu.ops.short_conv import causal_conv
from mlops_tpu.ops.ssd import CHUNK, _read_slots, ssd_scan

H, P, G, N = 4, 8, 2, 16  # heads of P channels, G groups, a state of N
CHUNK_T = 16  # the tests' chunk


def recurrence(x, dt, a, b, c, skip):
    """The definition, float64: H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T,
    y_t = H_t C_t + D x_t, a state a head, zero before position 0."""
    batch, seq, heads, width = x.shape
    share = heads // b.shape[2]
    y = np.zeros((batch, seq, heads, width))
    for i in range(batch):
        state = np.zeros((heads, width, b.shape[-1]))
        for t in range(seq):
            b_t, c_t = np.repeat(b[i, t], share, axis=0), np.repeat(c[i, t], share, axis=0)
            fed = (dt[i, t][:, None] * x[i, t])[:, :, None] * b_t[:, None, :]
            state = np.exp(dt[i, t] * a)[:, None, None] * state + fed
            y[i, t] = np.einsum("hpn,hn->hp", state, c_t) + skip[:, None] * x[i, t]
    return y


def drawn(seq, batch=2, heads=H, groups=G, seed=0, slow=True):
    """Inputs whose slow heads remember far past a chunk: dt log-uniform in
    [0.001, 0.5], A = -(1..H)."""
    rng = np.random.default_rng([seed, seq])
    top = 0.5 if slow else 3.0
    return dict(
        x=rng.normal(size=(batch, seq, heads, P)),
        dt=np.exp(rng.uniform(np.log(0.001), np.log(top), size=(batch, seq, heads))),
        a=-np.arange(1.0, heads + 1),
        b=rng.normal(size=(batch, seq, groups, N)),
        c=rng.normal(size=(batch, seq, groups, N)),
        skip=rng.normal(size=heads),
    )


def scan(z, **kw):
    kw.setdefault("chunk", CHUNK_T)
    kw.setdefault("dtype", jnp.float32)
    return np.asarray(ssd_scan(*(jnp.asarray(z[k], jnp.float32) for k in "x dt a b c skip".split()), **kw))


def test_the_published_chunk_is_the_default():
    assert CHUNK == 128


@pytest.mark.parametrize("seq", [64, 50, 9, 16, 17, 1],
                         ids=["whole-chunks", "ragged", "shorter-than-one", "one-chunk", "one-over", "one-position"])
def test_the_chunked_scan_is_the_recurrence(seq):
    z = drawn(seq)
    want = recurrence(**z)
    got = scan(z)
    assert got.shape == want.shape and got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=2e-5 * max(1.0, np.abs(want).max()))
    assert np.abs(want).max() > 1.0


@pytest.mark.parametrize("chunk", [1, 5, 16, 64, 128], ids=lambda c: f"chunk-{c}")
def test_any_chunk_gives_the_same_answers(chunk):
    z = drawn(40)
    np.testing.assert_allclose(scan(z, chunk=chunk), recurrence(**z), atol=1e-4)


@pytest.mark.parametrize("groups", [1, 2, 4], ids=["one-group", "two-groups", "a-group-a-head"])
def test_heads_read_their_groups_b_and_c(groups):
    z = drawn(40, groups=groups, seed=groups)
    np.testing.assert_allclose(scan(z), recurrence(**z), atol=1e-4)


def test_the_state_is_carried_across_chunks_and_never_across_histories():
    """A change to a token of chunk 0 moves answers in every later chunk as
    the recurrence says, and nothing in the batch's other history."""
    z = drawn(64)
    moved = {**z, "x": z["x"].copy()}
    moved["x"][0, 3] += 2.0
    base, after = scan(z), scan(moved)
    want = recurrence(**moved) - recurrence(**z)
    np.testing.assert_allclose(after - base, want, atol=1e-4)
    for chunk_start in (16, 32, 48):  # the slow heads still hold it there
        assert np.abs(after[0, chunk_start:chunk_start + 16] - base[0, chunk_start:chunk_start + 16]).max() > 1e-2
    np.testing.assert_array_equal(after[0, :3], base[0, :3])  # causal
    np.testing.assert_array_equal(after[1], base[1])  # the other history


def test_a_program_that_drops_the_carried_state_is_told_apart():
    """What the cell's limits have to catch: with dt and A as the family
    initialises them the state a chunk hands on is seen in its answers."""
    z = drawn(64)
    whole = recurrence(**z)
    alone = np.concatenate(  # every chunk from a zero state
        [recurrence(**{k: (v[:, s:s + 16] if np.ndim(v) > 1 else v) for k, v in z.items()})
         for s in range(0, 64, 16)], axis=1,
    )
    assert np.abs(whole[:, 16:] - alone[:, 16:]).max() > 0.1
    np.testing.assert_allclose(scan(z)[:, :16], alone[:, :16], atol=1e-4)


def test_fast_heads_forget_within_a_chunk():
    """The regime `benchmark/inputs.py`'s own rule for a bias would give (dt
    near 0.7, A near -1 and below): nothing crosses 16 positions that a
    comparison could see (eight positions into a chunk what came before it
    has decayed by exp(-24) at least); why the driver sets the per-head
    leaves."""
    z = drawn(64, slow=False)
    z["dt"] = np.full_like(z["dt"], 3.0)
    whole = recurrence(**z)
    alone = np.concatenate(
        [recurrence(**{k: (v[:, s:s + 16] if np.ndim(v) > 1 else v) for k, v in z.items()})
         for s in range(0, 64, 16)], axis=1,
    )
    late = np.arange(64) % 16 >= 8
    assert np.abs(whole[:, late] - alone[:, late]).max() < 1e-6


@pytest.mark.parametrize("short", [1, 20, 47], ids=lambda n: f"{n}-real-positions")
def test_positions_padded_behind_a_history_change_no_answer(short):
    z = drawn(48)
    cut = {k: (v[:, :short] if np.ndim(v) > 1 else v) for k, v in z.items()}
    np.testing.assert_allclose(scan(z)[:, :short], scan(cut), atol=1e-5)


@pytest.mark.parametrize("read", [
    [15, 31, 47, 63], [0], [63], [3, 4, 5, 40], [7, 9, 11, 13, 15, 17, 62], list(range(64)),
], ids=["chunk-ends", "first", "last", "three-in-a-chunk-none-in-two", "straddling", "every"])
def test_the_read_form_is_the_full_form_at_the_read_positions(read):
    z = drawn(64)
    read = np.asarray(read)
    full = scan(z)
    some = scan({**z, "c": z["c"][:, read]}, read=read)
    assert some.shape == (2, len(read), H, P)
    np.testing.assert_allclose(some, full[:, read], atol=1e-5)


def test_the_read_form_on_a_ragged_history_and_record_ends():
    """48 tokens a record against a chunk of 32: what the model asks."""
    z = drawn(3 * 48 + 5)
    read = 48 * np.arange(1, 4) - 1
    some = scan({**z, "c": z["c"][:, read]}, read=read, chunk=32)
    np.testing.assert_allclose(some, recurrence(**z)[:, read], atol=1e-4)


def test_read_slots_lay_the_read_positions_out_by_chunk():
    within, source, flat = _read_slots(np.array([3, 4, 40, 63]), 16, 4)
    assert within.shape == source.shape == (4, 2)
    assert within[0].tolist() == [3, 4] and source[0].tolist() == [0, 1]
    assert within[2, 0] == 8 and source[2, 0] == 2 and within[3, 0] == 15
    assert flat.tolist() == [0, 1, 4, 6]
    assert within[1].tolist() == [0, 0]  # a chunk nobody reads: computed, never picked


def test_bfloat16_operands_are_near_and_the_state_stays_float32():
    z = drawn(64)
    want = recurrence(**z)
    got = scan(z, dtype=jnp.bfloat16)
    gap = np.abs(got - want)
    assert 1e-4 < gap.max() < 0.05 * np.abs(want).max()
    text = jax.jit(
        lambda *t: ssd_scan(*t, chunk=CHUNK_T, dtype=jnp.bfloat16)
    ).lower(*(jnp.asarray(z[k], jnp.float32) for k in "x dt a b c skip".split())).as_text()
    # the chunk states: ONE float32 array, chunks first
    assert f"tensor<4x2x{G}x{H // G}x{P}x{N}xf32>" in text
    assert "bf16" in text


def test_nothing_a_history_wide_is_formed():
    """S = 512 in chunks of 64: no [S, S] array and no score wider than a
    chunk in the lowered program."""
    z = drawn(512, batch=1)
    text = jax.jit(lambda *t: ssd_scan(*t, chunk=64, dtype=jnp.float32)).lower(
        *(jnp.asarray(z[k], jnp.float32) for k in "x dt a b c skip".split())
    ).as_text()
    assert "512x512" not in text
    assert f"tensor<1x8x{G}x{H // G}x64x64xf32>" in text  # a chunk's decays


def test_the_scan_differentiates():
    z = drawn(40, batch=1)
    args = [jnp.asarray(z[k], jnp.float32) for k in "x dt a b c skip".split()]
    grads = jax.grad(
        lambda *t: jnp.sum(ssd_scan(*t, chunk=CHUNK_T, dtype=jnp.float32) ** 2), argnums=(0, 1, 3, 4)
    )(*args)
    assert all(np.isfinite(np.asarray(g)).all() and np.abs(np.asarray(g)).max() > 0 for g in grads)


@pytest.mark.parametrize("heads,groups,chunk,match", [
    (4, 3, 16, "4 heads over 3 groups"), (4, 2, 0, "chunks of 0"),
], ids=["ragged-groups", "no-chunk"])
def test_the_scans_guards(heads, groups, chunk, match):
    z = drawn(8, heads=heads, groups=groups)
    with pytest.raises(ValueError, match=match):
        scan(z, chunk=chunk)


# ------------------------------------------------------- the convolution
def convolved(x, taps, bias):
    """The definition, float64: y[t] = silu(bias + sum_j w[j] x[t - (L - 1)
    + j]), zeros left of position 0."""
    width = taps.shape[0]
    padded = np.concatenate([np.zeros((x.shape[0], width - 1, x.shape[2])), x], axis=1)
    mixed = sum(taps[j] * padded[:, j:j + x.shape[1]] for j in range(width)) + bias
    return mixed / (1.0 + np.exp(-mixed))


@pytest.mark.parametrize("width", [1, 3, 4], ids=lambda w: f"{w}-taps")
def test_the_plain_convolution_is_its_definition(width):
    rng = np.random.default_rng(width)
    x, taps, bias = rng.normal(size=(2, 11, 6)), rng.normal(size=(width, 6)), rng.normal(size=6)
    out = causal_conv(jnp.asarray(x, jnp.float32), jnp.asarray(taps, jnp.float32),
                      jnp.asarray(bias, jnp.float32))
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(out, convolved(x, taps, bias), atol=1e-5)


def test_the_plain_convolution_is_causal_and_reads_three_back():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 12, 5)).astype(np.float32)
    taps, bias = rng.normal(size=(4, 5)).astype(np.float32), np.zeros(5, np.float32)
    base = np.asarray(causal_conv(jnp.asarray(x), jnp.asarray(taps), jnp.asarray(bias)))
    moved = x.copy()
    moved[0, 6] += 1.0
    after = np.asarray(causal_conv(jnp.asarray(moved), jnp.asarray(taps), jnp.asarray(bias)))
    changed = np.abs(after - base).max(axis=(0, 2)) > 0
    assert changed.tolist() == [False] * 6 + [True] * 4 + [False] * 2
    # bfloat16 in, float32 out: what follows rounds each part once
    assert causal_conv(jnp.asarray(x, jnp.bfloat16), jnp.asarray(taps), jnp.asarray(bias)).dtype == jnp.float32


def test_the_plain_convolutions_guard():
    with pytest.raises(ValueError, match="5 channels"):
        causal_conv(jnp.zeros((1, 4, 5)), jnp.zeros((4, 6)), jnp.zeros(6))
