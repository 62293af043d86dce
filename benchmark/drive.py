"""Driving the CLI in-process with one live engine.

The benchmark runs whole campaigns through the entry points a user
calls (`python -m madsim_tpu explore|hunt|regress|audit`, in-process:
a chip belongs to one process). As the fleet worker holds one live
engine per engine key (`fleet/worker.py`), a `Session` builds ONE
engine — with the CLI's own `_build_engine`, from flags parsed by the
CLI's own parser — and hands it to every later campaign. This is the
spy pattern of `chip_smoke.run_cli` (PR 21), copied so that a change to
that script cannot move the benchmark.

What the session records, all on the host's clock (`time.perf_counter`):

* every `Engine.run_stream` call: start, end, seeds asked, the result's
  counts and `stats` (the executor's own exact counters);
* every `_stream_batches` aggregate (kept whole: the CLI prints only the
  first 20 failing seeds);
* every compile request jax makes (`backend_compile_duration` events:
  trace + lower + compile-or-read-from-the-cache), with its function name;
* in a traced run only, the program's own host spans (`perf/recorder.py`:
  dispatch, counters_poll, ring_drain, compile, init, harvest), each also
  written into the profiler's trace as `bench:<name>` so that idle gaps
  on the device can be named by what the host was doing.

No `MADSIM_TPU_*` gate is set: the traced run compiles the program the
timed run compiled.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from unittest import mock

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
ANNOTATION_PREFIX = "bench:"


def say(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass
class CliRun:
    rc: int
    t0: float
    t1: float
    args: object = None  # the CLI's parsed namespace (None: no stream ran)
    agg: dict | None = None  # `_stream_batches`' aggregate


def annotated_recorder():
    """A `PerfRecorder` whose spans are also `TraceAnnotation`s, so the
    profiler's trace holds them on the clock of the device events."""
    import jax

    from madsim_tpu.perf.recorder import PerfRecorder

    class AnnotatedRecorder(PerfRecorder):
        @contextlib.contextmanager
        def span(self, name, **args):
            with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name), \
                    super().span(name, **args):
                yield self

    return AnnotatedRecorder(meta={"cmd": "benchmark"})


class Session:
    """One engine, many campaigns. `traced` turns the recorder on."""

    def __init__(self, workdir: str, traced: bool = False):
        self.workdir = workdir
        self.traced = traced
        self.eng = None
        self.engine_build_s = None
        self.stream_calls: list = []  # dicts, one per Engine.run_stream call
        self.compiles: list = []  # (t, fun_name, seconds)
        self.cache_misses: list = []  # t of each persistent-cache miss
        self.stream_args = None  # the CLI's parsed flags of the last stream
        self.recorder = None
        self._recorder_t0 = None

    # -- jax's compile events ------------------------------------------------

    def listen_for_compiles(self) -> None:
        import jax

        def on_duration(event: str, seconds: float, **kw) -> None:
            if event == BACKEND_COMPILE_EVENT:
                self.compiles.append(
                    (time.perf_counter(), str(kw.get("fun_name", "?")), seconds)
                )

        def on_event(event: str, **_kw) -> None:
            if event == CACHE_MISS_EVENT:
                self.cache_misses.append(time.perf_counter())

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def compiles_between(self, t0: float, t1: float) -> list:
        return [c for c in self.compiles if t0 <= c[0] <= t1]

    # -- the program's host spans (traced run) -------------------------------

    @contextlib.contextmanager
    def recording(self):
        if not self.traced:
            yield
            return
        self.recorder = annotated_recorder()
        with self.recorder:
            self._recorder_t0 = time.perf_counter()
            yield

    def spans(self) -> list:
        """The recorder's closed spans as (name, t0, t1) on perf_counter."""
        if self.recorder is None:
            return []
        base = self._recorder_t0
        return [
            (s["name"], base + s["ts"] / 1e6, base + (s["ts"] + s["dur"]) / 1e6)
            for s in self.recorder.spans if s["dur"] is not None
        ]

    def span(self, name: str):
        """A span of the benchmark's own, around a call into a layer."""
        if self.recorder is None:
            return contextlib.nullcontext()
        return self.recorder.span(name)

    # -- the engine -----------------------------------------------------------

    def _hold(self, eng):
        """Record every `run_stream` call of the held engine."""
        inner = eng.run_stream
        calls = self.stream_calls

        def run_stream(n_seeds, *a, **kw):
            t0 = time.perf_counter()
            out = inner(n_seeds, *a, **kw)  # opens its own `run_stream` span
            calls.append({
                "t0": t0, "t1": time.perf_counter(), "n_seeds": int(n_seeds),
                "completed": out["completed"],
                "seeds_consumed": out["seeds_consumed"],
                "failing": len(out["failing"]),
                "stats": {k: v for k, v in out["stats"].items()
                          if isinstance(v, (int, float, bool))},
            })
            return out

        eng.run_stream = run_stream
        self.eng = eng
        return eng

    def cli(self, argv: list) -> CliRun:
        """`python -m madsim_tpu <argv>` in this process. The first call
        that builds an engine builds it for real and the session keeps
        it; later calls get that engine."""
        import madsim_tpu.__main__ as cli

        seen: dict = {}
        real_build = cli._build_engine
        real_stream = cli._stream_batches

        def build(args):
            if self.eng is None:
                t0 = time.perf_counter()
                self._hold(real_build(args))
                self.engine_build_s = time.perf_counter() - t0
            return self.eng

        def spy(eng, args, purpose="explore"):
            agg = real_stream(eng, args, purpose=purpose)
            seen.update(args=args, agg=agg)
            self.stream_args = args
            return agg

        say(f"$ python -m madsim_tpu {' '.join(argv)}")
        t0 = time.perf_counter()
        with mock.patch.object(cli, "_build_engine", build), \
                mock.patch.object(cli, "_stream_batches", spy):
            rc = cli.main(list(argv))
        return CliRun(int(rc or 0), t0, time.perf_counter(),
                      seen.get("args"), seen.get("agg"))


def flag_argv(flags: dict) -> list:
    """{"horizon": 5, "coverage": true} -> ["--horizon", "5", "--coverage"]."""
    argv = []
    for key, value in flags.items():
        if value is False or value is None:
            continue
        argv.append("--" + key)
        if value is not True:
            argv.append(str(value))
    return argv


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(n_devices: int):
    """Peak bytes in use on the fullest of the first `n_devices`."""
    import jax

    peaks = []
    for d in jax.devices()[:n_devices]:
        try:
            m = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — a backend without the API
            m = {}
        if "peak_bytes_in_use" in m:
            peaks.append(int(m["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def list_cpu_backend() -> None:
    """Replay, shrink and the CPU oracle run on jax's CPU backend in this
    same process, so it must be initialised beside the accelerator. The
    named platform stays first — still the default, and jax still fails
    at start-up if it cannot be initialised. Call before importing jax."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
