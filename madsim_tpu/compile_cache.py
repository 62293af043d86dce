"""JAX persistent compilation cache wiring (+ warm-start keys).

Hunts, sweeps and CI shards spawn many processes over the same configs,
so they should pay each compile once per *machine*, not once per
*process*. The cache is therefore ON by default, at one fixed place.

Where the cache lives (`enable_compile_cache`):

  * `JAX_COMPILATION_CACHE_DIR` set: jax itself already points there.
    That directory is the active one whatever `--compile-cache`,
    `$MADSIM_TPU_COMPILE_CACHE` or `EngineConfig.compile_cache_dir`
    say, nothing is nested under it, and this module never touches
    `jax_compilation_cache_dir` — whoever runs the program (a chip
    tool, a CI job) places the cache from outside.
  * otherwise `--compile-cache` / `$MADSIM_TPU_COMPILE_CACHE` /
    `EngineConfig.compile_cache_dir`, else `DEFAULT_CACHE_DIR`
    (`<checkout>/.madsim-jit-cache`, gitignored). Never a temporary
    name, a pid or a time: the directory is where the NEXT process
    looks, so a path that moves never hits.

The cache is keyed by (HLO, jaxlib version, XLA flags, device kind), so
it is safe to share a directory across configs, backends and machines
of the same software image; a mismatched key is simply a miss.

`cache_subkey` renders the (jax version, gate tuple, stream version,
shape, topology) tuple as a directory-name-safe string: the fleet
allocator groups same-compile jobs by it and the AOT artifacts below
are filed under it. It is not part of the XLA cache path.

Failure discipline: an unwritable directory is probed by an actual
write: `strict=True` (the benchmark, chip_smoke.py) raises; the default
logs a warning and leaves the cache off — never a silent no-op that
lets a fleet believe it is warm while every worker recompiles.
"""

from __future__ import annotations

import contextlib
import logging
import os
import re
from typing import Optional

_active_dir: Optional[str] = None

#: where the cache lives when nothing outside the program places it
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".madsim-jit-cache",
)

_log = logging.getLogger("madsim_tpu.compile_cache")

# -- AOT supersegment serialization (r12) ------------------------------------
#
# The persistent XLA cache above removes the *compile* half of a warm
# worker's start cost; round 11 measured the remaining 18.2 s flagship
# warm start as TRACE-dominated — jax re-traces the streaming program
# every process even when the executable deserializes. `jax.export`
# closes that half: the engine serializes the exported (traced +
# lowered) supersegment under $MADSIM_TPU_AOT_CACHE keyed by the
# warm-start subkey PLUS a sha1 fingerprint of the package sources and
# the full engine/machine configuration, so a warm worker deserializes
# StableHLO instead of re-tracing Python. The fingerprint is the
# staleness guard: jax's internal cache key protects the *executable*
# layer, but a deserialized export IS the program — a stale artifact
# must be a miss, never a silently different trace. Load/save are
# best-effort (corrupt or unwritable entries degrade to a plain
# re-trace, logged); `_AOT_SCHEMA` bumps invalidate every entry.

_AOT_SCHEMA = 1
_aot_disabled = False
_src_fingerprint: Optional[str] = None


def aot_cache_dir() -> Optional[str]:
    """The AOT artifact directory ($MADSIM_TPU_AOT_CACHE), or None."""
    return os.environ.get("MADSIM_TPU_AOT_CACHE") or None


def aot_enabled() -> bool:
    """True when AOT serialization is configured and not suspended."""
    return aot_cache_dir() is not None and not _aot_disabled


@contextlib.contextmanager
def disable_aot():
    """Suspend AOT load/save for the dynamic extent — the honest
    no-AOT warm path `measure_warm_compile(cold_trace=True)` times."""
    global _aot_disabled
    prev = _aot_disabled
    _aot_disabled = True
    try:
        yield
    finally:
        _aot_disabled = prev


def source_fingerprint() -> str:
    """sha1 over every .py source in the madsim_tpu package (sorted
    relative-path walk) — the part of an AOT artifact's identity the
    warm-start subkey cannot see. Computed once per process: the
    sources don't change under a running engine, and a fleet's many
    _stream_fns builds must not re-hash the tree each time."""
    global _src_fingerprint
    if _src_fingerprint is None:
        import hashlib

        root = os.path.dirname(os.path.abspath(__file__))
        h = hashlib.sha1()
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = sorted(
                d for d in dirnames if d != "__pycache__"
            )
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
        _src_fingerprint = h.hexdigest()[:16]
    return _src_fingerprint


def _aot_path(subkey: str, name: str) -> Optional[str]:
    base = aot_cache_dir()
    if base is None:
        return None
    base = os.path.abspath(os.path.expanduser(base))
    return os.path.join(
        base, f"schema{_AOT_SCHEMA}", subkey, f"{name}.jaxexp"
    )


def load_aot(subkey: str, name: str) -> Optional[bytes]:
    """Read a serialized export, or None (disabled / missing). The
    caller deserializes and falls back to a live trace on failure."""
    if not aot_enabled():
        return None
    path = _aot_path(subkey, name)
    try:
        with open(path, "rb") as f:
            return f.read()
    except OSError:
        return None


def save_aot(subkey: str, name: str, blob: bytes) -> Optional[str]:
    """Atomically persist a serialized export (tmp + rename, so a
    concurrent fleet worker never reads a torn artifact). Best-effort:
    an unwritable directory logs and returns None — the process keeps
    its live trace."""
    if not aot_enabled():
        return None
    path = _aot_path(subkey, name)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)
    except OSError as e:
        _log.warning("could not persist AOT artifact %s: %s", path, e)
        return None
    return path


def cache_subkey(
    *,
    gates: Optional[dict] = None,
    rng_stream: Optional[int] = None,
    lanes: Optional[int] = None,
    segment_steps: Optional[int] = None,
    devices: Optional[int] = None,
    import_jax: bool = True,
) -> str:
    """A directory-name-safe warm-start key: (jax/jaxlib version, gate
    tuple, stream version, shape key, device topology). Two processes
    with equal subkeys compile byte-identical HLO for the streaming
    path, so priming one warms the other; anything that changes the
    compiled step (a jax upgrade, a gate flip, a new lane count, a
    different mesh shape) lands in its own subdirectory instead of
    growing one stale shared pile forever.

    `devices` is the 1-D "batch" mesh size the program spans (1 =
    unsharded). It is part of the key because a serialized AOT export
    is topology-specific — a single-device export must never
    deserialize into a mesh run and vice versa — and because the fleet
    allocator's warm-compile grouping must keep a mesh job and a
    single-device job in different groups (their compiled programs
    share nothing).

    `gates` is a dict ({"rng_stream": 3, "coverage":
    True, ...}); bool values render as 0/1, the rest as-is. Unknown /
    None fields are simply omitted — the key is best-effort
    discrimination, jax's internal (HLO, jaxlib, flags, device) key is
    what guarantees correctness.

    `import_jax=False` pins the version prefix to `jax-unknown`
    WITHOUT touching jax (even when it is importable): the fleet
    control plane computes job-grouping subkeys jax-free, and a
    grouping key must be identical no matter which process renders it
    — the allocator needs EQUALITY, not version discrimination (jax's
    internal cache key still provides that for the actual entries)."""
    if not import_jax:
        parts = ["jax-unknown"]
    else:
        try:
            import jax
            import jaxlib

            parts = [f"jax{jax.__version__}-jaxlib{jaxlib.__version__}"]
        except Exception:  # pragma: no cover - jax-free callers
            parts = ["jax-unknown"]
    if rng_stream is not None:
        parts.append(f"rng{rng_stream}")
    if gates:
        bits = []
        for k in sorted(gates):
            v = gates[k]
            if v is None:
                continue
            short = "".join(w[0] for w in k.split("_")) or k
            bits.append(f"{short}{int(v) if isinstance(v, bool) else v}")
        if bits:
            parts.append(".".join(bits))
    if lanes is not None:
        shape = f"l{lanes}"
        if segment_steps is not None:
            shape += f"x{segment_steps}"
        parts.append(shape)
    if devices is not None:
        parts.append(f"d{devices}")
    return re.sub(r"[^A-Za-z0-9._-]", "_", "-".join(parts))


def _probe_writable(path: str) -> Optional[str]:
    """Create `path` and prove a write lands. Returns an error string
    instead of raising (the caller decides strict vs warn). A plain
    os.access check is not enough: this repo's CI and the reference box
    run as root, where access() says yes to read-only mounts."""
    try:
        os.makedirs(path, exist_ok=True)
        probe = os.path.join(path, ".madsim-tpu-write-probe")
        with open(probe, "w") as f:
            f.write("ok")
        os.remove(probe)
    except OSError as e:
        return f"{type(e).__name__}: {e}"
    return None


def enable_compile_cache(
    path: Optional[str] = None,
    *,
    strict: bool = False,
) -> Optional[str]:
    """Enable the JAX persistent compilation cache; returns the active
    directory (see the module docstring for how it is chosen).

    Idempotent, and the first directory wins for the process (jax's
    cache is global): later calls return the ACTIVE directory rather
    than silently rebinding half the jit cache — so call it BEFORE the
    first jit.

    An unwritable directory raises RuntimeError under `strict` and
    logs a warning (cache left off, None returned) otherwise."""
    global _active_dir
    if _active_dir is not None:
        return _active_dir
    external = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if external:
        path = external
    else:
        path = os.path.abspath(os.path.expanduser(
            path or os.environ.get("MADSIM_TPU_COMPILE_CACHE") or DEFAULT_CACHE_DIR
        ))
    err = _probe_writable(path)
    if err is not None:
        msg = (
            f"compile cache directory {path!r} is not writable ({err}); "
            f"every process will silently recompile"
        )
        if strict:
            raise RuntimeError(msg)
        _log.warning("%s — persistent cache left DISABLED", msg)
        return None
    import jax
    from jax.experimental.compilation_cache import compilation_cache as _cc

    # cache wiring lands on the host timeline (madsim_tpu/perf) so a
    # --perf-timeline run shows whether its compiles could hit a
    # persistent cache at all
    from .perf import compile_log
    from .perf.recorder import maybe_count

    maybe_count("compile_cache_enabled")
    # compile stages by program, from here on (perf/compile_log.py)
    compile_log.install()
    if not external:
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every compile, not just the multi-second ones: a hunt's many
    # small jits (replay steps, shrink candidates) add up too. -1 on the
    # entry-size floor disables the filesystem-specific override that 0
    # would allow (which can silently skip small entries).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the cache module latches "no cache" on the first compile of the
    # process; a reset makes the next compile re-initialize against the
    # directory just configured (no-op if nothing compiled yet)
    _cc.reset_cache()
    _active_dir = path
    return _active_dir


def active_compile_cache() -> Optional[str]:
    """The directory enabled for this process, or None."""
    return _active_dir


def cache_entry_count() -> int:
    """Executables in the active cache directory (0 when the cache is
    off) — what a run prints before and after so a second run shows it
    wrote nothing new."""
    if _active_dir is None:
        return 0
    return sum(name.endswith("-cache") for name in os.listdir(_active_dir))


def measure_warm_compile(build_and_run, cold_trace: bool = False) -> Optional[float]:
    """Time the WARM compile path: drop every in-process jit cache,
    then run `build_and_run` (which must construct fresh jitted
    callables and force their compilation — invoke once, or compile
    without executing via `.lower().compile()` so device execution
    stays out of the timed window) against the
    persistent entries the cold path just wrote — the exact path a new
    fleet worker or a post-restart replay pays. Returns seconds, or
    None when no persistent cache is active (there is no warm path to
    measure; the honest answer is "same as cold", not a fabricated
    number).

    `cold_trace=True` additionally suspends the AOT export cache for
    the rebuild: the r11 number silently *included* any AOT entries
    the cold run wrote, so "warm" conflated deserialize-the-trace with
    re-trace-everything. The two are now separately measurable — warm
    (AOT allowed, the real fleet-worker path) vs cold-trace (persistent
    XLA cache only, every trace re-paid), and tests/test_perf.py
    asserts warm-with-AOT beats warm-without."""
    if _active_dir is None:
        return None
    import time

    import jax

    jax.clear_caches()
    ctx = disable_aot() if cold_trace else contextlib.nullcontext()
    with ctx:
        t0 = time.perf_counter()  # madsim: allow(D001) — host-side timing
        build_and_run()
        return time.perf_counter() - t0  # madsim: allow(D001)
