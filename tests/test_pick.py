"""`engine.machine.get_at`: a lane's read of one row of a small per-lane
table as a one-hot select-reduce (ROADMAP S9).

Three guards:
  * the helper against plain `arr[i]` / `arr[i, j]`, bit for bit, for
    every index from -2n to 2n (a negative index wraps once, then the
    index is clamped), under `jit` and `vmap`;
  * an oracle per benchmarked engine: with the helper monkeypatched to
    the plain gather, K steps of `step_batch` give an identical state
    tree;
  * the engagement count: the `gather` / `dynamic_slice` equations left
    in one `step_batch`'s jaxpr per engine, pinned, so an edit that
    brings a gather back into the step fails here.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import madsim_tpu.__main__ as cli  # noqa: E402
from benchmark import cells  # noqa: E402
from madsim_tpu.engine import Engine  # noqa: E402
from madsim_tpu.engine import core as engine_core  # noqa: E402
from madsim_tpu.engine.machine import get_at  # noqa: E402
from madsim_tpu.models import etcd_mvcc, kafka, kvraft, raft  # noqa: E402

WIDTHS = (5, 10, 9, 65, 97, 257)
DTYPES = (jnp.int32, jnp.uint32, jnp.bool_)


def _table(shape, dtype, salt):
    rng = np.random.default_rng(salt)
    if dtype == jnp.bool_:
        return jnp.asarray(rng.integers(0, 2, shape).astype(bool))
    # the whole word, sign bit and top bit included
    words = rng.integers(0, 2**32, shape, dtype=np.uint64).astype(np.uint32)
    return jnp.asarray(words.view(np.int32) if dtype == jnp.int32 else words)


def _same(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _edges(n):
    return [-2 * n, -n - 1, -n, -1, 0, n - 1, n, 2 * n]


@pytest.mark.parametrize("kind", ("word", "row", "cell"))
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: np.dtype(d).name)
def test_helper_reads_what_plain_indexing_reads(dtype, width, kind):
    if kind == "cell":
        # `log_term[node, k]`: a [5, W] table, every (i, j)
        t = _table((5, width), dtype, width)
        ii, jj = np.meshgrid(np.arange(-10, 11), np.arange(-2 * width, 2 * width + 1))
        idx = (jnp.asarray(ii.ravel(), jnp.int32), jnp.asarray(jj.ravel(), jnp.int32))
        helper = lambda i, j: get_at(t, (i, j))  # noqa: E731
        plain = lambda i, j: t[i, j]  # noqa: E731
        edges = [(i, j) for i in _edges(5)[::3] for j in _edges(width)]
    else:
        # `term[node]` -> a word; `match_idx[node]` -> a row
        t = _table((width,) if kind == "word" else (width, 4), dtype, width)
        idx = (jnp.arange(-2 * width, 2 * width + 1, dtype=jnp.int32),)
        helper = lambda i: get_at(t, i)  # noqa: E731
        plain = lambda i: t[i]  # noqa: E731
        edges = [(i,) for i in _edges(width)]
    _same(jax.jit(jax.vmap(helper))(*idx), jax.jit(jax.vmap(plain))(*idx))
    # one lane: the reference is a `dynamic_slice`, not a batched gather
    jh, jp = jax.jit(helper), jax.jit(plain)
    for e in edges:
        e = tuple(jnp.int32(x) for x in e)
        _same(jh(*e), jp(*e))


def test_helper_takes_an_unsigned_index_and_an_index_vector():
    t = _table((9, 3), jnp.int32, 1)
    for i in (0, 8, 9, 18):
        _same(get_at(t, jnp.uint32(i)), t[jnp.uint32(i)])
    order = jnp.asarray([3, -1, 7, -9, 0, 99], jnp.int32)
    owned = _table((9,), jnp.bool_, 2)
    f = jax.jit(jax.vmap(lambda o: (get_at(t, o), get_at(owned, o))))
    g = jax.jit(jax.vmap(lambda o: (t[o], owned[o])))
    offs = jnp.arange(4, dtype=jnp.int32)[:, None] + order[None, :]
    for got, want in zip(f(offs), g(offs)):
        _same(got, want)


# -- the benchmarked engines ---------------------------------------------------

ENGINES = {
    "raft5": "raft5_sweep",
    "raft5_fig8": "raft5_fig8_sweep",
    "kafka_pc5": "kafka_pc5_sweep",
    "kvraft5": "kvraft5_sweep",
    "etcd_mvcc4": "etcd_mvcc4_hunt",
}
# every module that holds the helper under its own name
USERS = (engine_core, raft, kafka, kvraft, etcd_mvcc)
LANES, STEPS = 6, 320


def _engine(config: str) -> Engine:
    """The engine a cell of `config` runs, from the cell's own argv, on
    the XLA step path (what the CPU backend and a mesh run)."""
    cell = cells.load_cell(ENGINES[config])
    campaign = cells.load_campaign(cell)
    extra = () if cell.kind == "sweep" else ("/nonexistent/corpus.json",)
    argv = campaign.argv(cell, 1_000_000, *extra)
    seen = {}

    def build(args):
        seen["args"] = args
        raise SystemExit(0)  # parsed: the test builds the engine itself

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cli, "_build_engine", build)
        with pytest.raises(SystemExit):
            cli.main(list(argv))
    built = cli._build_engine(seen["args"])
    return Engine.on_xla_step_path(built.machine, built.config)


def _run(eng: Engine):
    state = eng.init_batch(jnp.arange(1_000_000, 1_000_000 + LANES))
    many = jax.jit(lambda s: lax.fori_loop(0, STEPS, lambda _, c: eng.step_batch(c), s))
    return jax.tree.map(np.asarray, many(state))


@pytest.mark.parametrize("config", ENGINES)
def test_engine_steps_as_with_the_plain_gather(config, monkeypatch):
    got = _run(_engine(config))
    for mod in USERS:
        monkeypatch.setattr(mod, "get_at", lambda arr, i: arr[i])
    want = _run(_engine(config))
    # the lanes did run: handlers sent messages in every lane, not in lockstep
    assert (want.msg_count > 5).all() and len(set(want.now_us.tolist())) > 1
    flat_got, tree = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree == tree_want
    for path, a, b in zip(jax.tree.leaves_with_path(want), flat_got, flat_want):
        assert a.dtype == b.dtype, path[0]
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path[0]))


def _count(jaxpr, names, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            out[eqn.primitive.name] = out.get(eqn.primitive.name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count(sub, names, out)
    return out


@pytest.mark.parametrize("config", ENGINES)
def test_no_gather_is_left_in_the_step(config):
    """One `step_batch` on the XLA step path, where the pop and the
    event's gather are the step's own too: no traced-index read is left
    as a `gather` (under vmap, what a `dynamic_slice` becomes). A
    machine handler, an invariant or the step that reads `table[i]`
    plainly again counts here."""
    eng = _engine(config)
    state = eng.init_batch(jnp.arange(4))
    jaxpr = jax.make_jaxpr(eng.step_batch)(state).jaxpr
    assert _count(jaxpr, ("gather", "dynamic_slice"), {}) == {}
    # with the helper off the reads come back: the count is not vacuous
    with pytest.MonkeyPatch.context() as mp:
        for mod in USERS:
            mp.setattr(mod, "get_at", lambda arr, i: arr[i])
        plain = jax.make_jaxpr(_engine(config).step_batch)(state).jaxpr
    assert _count(plain, ("gather", "dynamic_slice"), {}).get("gather", 0) >= 15
