"""Pallas event-pop kernels vs the XLA path — must agree bit-for-bit.

Runs the kernels in interpreter mode (no TPU needed); the
compiled-on-TPU path shares the same trace. Covers both the pop-only
kernel and the fused pop+gather kernel (the default TPU path since
rng/pop/clog PR) over the queue capacities {32, 64} and payload widths
{4, 6} the shipped models use."""

import jax
import jax.numpy as jnp
import pytest

from madsim_tpu.ops import pop_earliest
from madsim_tpu.ops.pallas_pop import (
    pop_earliest_batch,
    pop_gather_batch,
    step_megakernel,
    step_rng_words_fused,
    threefry2x32_pair,
)


def _random_queues(key, lanes=32, q=96):
    k1, k2, k3 = jax.random.split(key, 3)
    times = jax.random.randint(k1, (lanes, q), 0, 1000, dtype=jnp.int32)
    seqs = jax.random.randint(k2, (lanes, q), 0, 10_000, dtype=jnp.int32)
    valid = jax.random.bernoulli(k3, 0.7, (lanes, q))
    return times, seqs, valid


def _random_event_queues(key, lanes, q, p):
    times, seqs, valid = _random_queues(key, lanes, q)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    kinds = jax.random.randint(k1, (lanes, q), 0, 3, dtype=jnp.int32)
    nodes = jax.random.randint(k2, (lanes, q), 0, 33, dtype=jnp.int32)
    # src includes -1 (timer events) — the one-hot gather-sum must be
    # exact for negatives too
    srcs = jax.random.randint(k3, (lanes, q), -1, 33, dtype=jnp.int32)
    payload = jax.random.randint(
        k4, (lanes, q, p), -(2**20), 2**20, dtype=jnp.int32
    )
    return times, seqs, valid, kinds, nodes, srcs, payload


def test_pallas_pop_matches_xla():
    for seed in range(5):
        times, seqs, valid = _random_queues(jax.random.PRNGKey(seed))
        xla_idx, xla_any = jax.vmap(pop_earliest)(times, seqs, valid)
        pl_idx, pl_any = pop_earliest_batch(times, seqs, valid, use_pallas=True, interpret=True)
        assert xla_any.tolist() == pl_any.tolist()
        # idx only meaningful where a valid event exists
        for lane in range(times.shape[0]):
            if bool(xla_any[lane]):
                assert int(xla_idx[lane]) == int(pl_idx[lane]), f"seed {seed} lane {lane}"


def test_pallas_pop_ties_and_empty():
    # equal times tie-break by seq; fully-empty lanes report any=False
    times = jnp.zeros((8, 16), jnp.int32)
    seqs = jnp.tile(jnp.arange(16, dtype=jnp.int32)[::-1], (8, 1))
    valid = jnp.ones((8, 16), bool).at[3].set(False)
    idx, any_valid = pop_earliest_batch(times, seqs, valid, use_pallas=True, interpret=True)
    assert not bool(any_valid[3])
    for lane in (0, 1, 2, 4):
        assert int(idx[lane]) == 15  # smallest seq sits at the last column


@pytest.mark.parametrize("q", [32, 64])
@pytest.mark.parametrize("p", [4, 6])
def test_fused_pop_gather_matches_xla(q, p):
    """Fused pop+gather vs the XLA reference: the full popped event
    tuple (idx, any, time, kind, node, src, payload) bit-for-bit, for
    the queue capacities and payload widths the models use."""
    for seed in range(3):
        arrs = _random_event_queues(jax.random.PRNGKey(seed), 24, q, p)
        xi, xa, (xt, xk, xn, xs, xp) = pop_gather_batch(*arrs, use_pallas=False)
        pi, pa, (pt, pk, pn, ps, pp) = pop_gather_batch(
            *arrs, use_pallas=True, interpret=True
        )
        assert xa.tolist() == pa.tolist()
        for lane in range(24):
            if not bool(xa[lane]):
                continue
            assert int(xi[lane]) == int(pi[lane]), (seed, lane)
            assert int(xt[lane]) == int(pt[lane])
            assert int(xk[lane]) == int(pk[lane])
            assert int(xn[lane]) == int(pn[lane])
            assert int(xs[lane]) == int(ps[lane])
            assert xp[lane].tolist() == pp[lane].tolist()


def test_fused_pop_gather_empty_lane_gathers_slot0():
    """All-invalid lanes report any=False and gather slot 0 on BOTH
    paths (XLA argmin over an all-sentinel row returns 0) — the step
    masks the values out, but they must still agree bit-for-bit."""
    arrs = list(_random_event_queues(jax.random.PRNGKey(5), 16, 32, 4))
    arrs[2] = arrs[2].at[3].set(False).at[9].set(False)
    xi, xa, xvals = pop_gather_batch(*arrs, use_pallas=False)
    pi, pa, pvals = pop_gather_batch(*arrs, use_pallas=True, interpret=True)
    assert not bool(xa[3]) and not bool(pa[3])
    for lane in (3, 9):
        assert int(xi[lane]) == int(pi[lane]) == 0
        for xv, pv in zip(xvals, pvals):
            assert xv[lane].tolist() == pv[lane].tolist()


def test_fused_pop_gather_unaligned_lane_count():
    arrs = _random_event_queues(jax.random.PRNGKey(11), 13, 32, 6)
    xi, xa, xvals = pop_gather_batch(*arrs, use_pallas=False)
    pi, pa, pvals = pop_gather_batch(*arrs, use_pallas=True, interpret=True)
    assert pi.shape == (13,)
    assert xa.tolist() == pa.tolist()
    for xv, pv in zip(xvals, pvals):
        assert xv.tolist() == pv.tolist()


def test_pallas_pop_unaligned_lane_count():
    # non-multiple-of-8 lane counts are padded internally (review regression)
    times, seqs, valid = _random_queues(jax.random.PRNGKey(9), lanes=13, q=32)
    xla_idx, xla_any = jax.vmap(pop_earliest)(times, seqs, valid)
    pl_idx, pl_any = pop_earliest_batch(times, seqs, valid, use_pallas=True, interpret=True)
    assert pl_idx.shape == (13,)
    assert xla_any.tolist() == pl_any.tolist()
    for lane in range(13):
        if bool(xla_any[lane]):
            assert int(xla_idx[lane]) == int(pl_idx[lane])


# -- the whole-event step megakernel (r11) -----------------------------------


def test_threefry_pair_matches_jax_primitive():
    """The in-kernel Threefry-2x32 (threefry2x32_pair + the pad/split
    packing in step_rng_words_fused) is bit-exact vs jax's fused
    primitive for odd AND even block widths — this IS the v3 stream
    contract: a single differing bit would silently re-derive every
    word a megakernel step consumes."""
    from jax.extend.random import threefry_2x32

    for seed in range(4):
        key = jax.random.PRNGKey(seed)
        for w in (1, 2, 7, 10, 11, 21, 22, 30):
            for step in (0, 3, 77, 123456):
                counts = jnp.uint32(step) * jnp.uint32(w) + jnp.arange(
                    w, dtype=jnp.uint32
                )
                ref = threefry_2x32(key, counts)
                fused = step_rng_words_fused(
                    key[None, :1].astype(jnp.uint32),
                    key[None, 1:].astype(jnp.uint32),
                    jnp.full((1, 1), step, jnp.uint32),
                    w,
                )[0]
                assert ref.tolist() == fused.tolist(), (seed, w, step)


def _oracle_step_prefix(arrs, keys, steps, w, d0=None, d1=None):
    """The XLA composition the megakernel must match bit-for-bit:
    pop+gather, then step_words_v3 per lane, then (optionally) the
    engine's digest fold over [tuple..., payload..., words...]."""
    from madsim_tpu.engine.core import digest_fold
    from madsim_tpu.ops.step_rng import step_words_v3

    idx, any_v, popped = pop_gather_batch(*arrs, use_pallas=False)

    class _Lay:  # step_words_v3 only reads these two fields
        total_words = w
        restart_off = None
        version = 3

    def words_of(key, step):
        _, words, _ = step_words_v3(key, step, _Lay)
        return words

    words = jax.vmap(words_of)(keys, steps)
    if d0 is None:
        return idx, any_v, popped, words, ()
    ev_time, ev_kind, ev_node, ev_src, ev_payload = popped

    def fold(dd0, dd1, t, k, n, s, pay, ws):
        return digest_fold(
            dd0, dd1,
            [t, k, n, s] + [pay[i] for i in range(pay.shape[0])]
            + [ws[i] for i in range(w)],
        )

    nd0, nd1 = jax.vmap(fold)(
        d0, d1, ev_time, ev_kind, ev_node, ev_src, ev_payload, words
    )
    return idx, any_v, popped, words, (nd0, nd1)


@pytest.mark.parametrize("q", [32, 64])
@pytest.mark.parametrize("p", [4, 6])
def test_step_megakernel_matches_xla(q, p):
    """Megakernel (interpreter mode) vs the XLA oracle: pop + gather +
    the v3 word block + the digest fold, bit-for-bit, over the queue
    capacities and payload widths the shipped models use — including an
    ODD block width (the threefry pad/split edge)."""
    from madsim_tpu.engine.core import digest_fold

    w = 21 if p == 4 else 22  # odd and even block widths both covered
    for seed in range(2):
        arrs = _random_event_queues(jax.random.PRNGKey(seed), 24, q, p)
        kk = jax.random.split(jax.random.PRNGKey(100 + seed), 24)
        keys = jnp.asarray(kk, jnp.uint32)
        steps = jax.random.randint(
            jax.random.PRNGKey(200 + seed), (24,), 0, 5000, dtype=jnp.int32
        )
        d0 = jax.random.bits(jax.random.PRNGKey(300 + seed), (24,), jnp.uint32)
        d1 = jax.random.bits(jax.random.PRNGKey(400 + seed), (24,), jnp.uint32)
        xi, xa, xpop, xw, (xd0, xd1) = _oracle_step_prefix(
            arrs, keys, steps, w, d0, d1
        )
        pi, pa, ppop, pw, (pd0, pd1) = step_megakernel(
            *arrs, keys, steps, w, d0=d0, d1=d1, digest_fold=digest_fold,
            interpret=True,
        )
        assert xa.tolist() == pa.tolist()
        assert xi.tolist() == pi.tolist()
        for xv, pv in zip(xpop, ppop):
            assert xv.tolist() == pv.tolist()
        assert xw.tolist() == pw.tolist()
        assert xd0.tolist() == pd0.tolist() and xd1.tolist() == pd1.tolist()


def test_step_megakernel_without_digest_and_unaligned():
    """Recorder-off variant (no digest operands/outputs at all) over an
    unaligned lane count: outputs sliced back, words still bit-exact."""
    arrs = _random_event_queues(jax.random.PRNGKey(9), 13, 32, 4)
    keys = jnp.asarray(jax.random.split(jax.random.PRNGKey(5), 13), jnp.uint32)
    steps = jnp.arange(13, dtype=jnp.int32) * 7
    xi, xa, xpop, xw, xdig = _oracle_step_prefix(arrs, keys, steps, 10)
    pi, pa, ppop, pw, pdig = step_megakernel(
        *arrs, keys, steps, 10, interpret=True
    )
    assert xdig == () and pdig == ()
    assert pi.shape == (13,) and pw.shape == (13, 10)
    assert xa.tolist() == pa.tolist() and xi.tolist() == pi.tolist()
    for xv, pv in zip(xpop, ppop):
        assert xv.tolist() == pv.tolist()
    assert xw.tolist() == pw.tolist()


@pytest.mark.parametrize("slots_log2", [7, 10])
@pytest.mark.parametrize("c", [4, 16])
def test_cov_flush_matches_sequential_oracle(slots_log2, c):
    """The VMEM coverage-flush kernel vs the vmapped sequential
    `coverage.cov_flush` oracle, bit-for-bit over the (map width,
    buffer depth) grid. The random buffers deliberately carry duplicate
    slots AND duplicate words within one buffer — the case a wide
    scatter would clobber (last-write-wins loses ORs); the kernel's
    one-hot OR accumulation and the oracle's sequential fold must agree
    exactly anyway. n spans 0 (nothing live), partial, and full."""
    from madsim_tpu.ops.pallas_pop import cov_flush_batch, cov_flush_pallas

    lanes = 37  # deliberately unaligned to LANE_BLOCK
    w = (1 << slots_log2) // 32
    key = jax.random.PRNGKey(slots_log2 * 100 + c)
    k1, k2, k3 = jax.random.split(key, 3)
    cov_map = jax.random.randint(
        k1, (lanes, w), -(2**31), 2**31 - 1, dtype=jnp.int32
    )
    # small slot range forces duplicate slots/words inside one buffer
    buf = jax.random.randint(k2, (lanes, c), 0, 1 << slots_log2, dtype=jnp.int32)
    buf = buf.at[:, : c // 2].set(buf[:, 0:1])  # hard duplicates
    n = jax.random.randint(k3, (lanes,), 0, c + 1, dtype=jnp.int32)
    n = n.at[0].set(0).at[1].set(c)  # pin the empty and full extremes
    oracle = cov_flush_batch(cov_map, buf, n, use_pallas=False)
    kernel = cov_flush_pallas(cov_map, buf, n, interpret=True)
    assert kernel.shape == (lanes, w)
    assert oracle.tolist() == kernel.tolist()
    # dead tails (i >= n) must never touch the map: a buffer of
    # out-of-range garbage with n=0 leaves the map bit-identical
    garbage = jnp.full((lanes, c), (1 << slots_log2) - 1, jnp.int32)
    zero_n = jnp.zeros((lanes,), jnp.int32)
    same = cov_flush_pallas(cov_map, garbage, zero_n, interpret=True)
    assert same.tolist() == cov_map.tolist()
