"""Divergence auditor — digest trails and first-divergent-step bisection.

The flight recorder (core.py) folds every popped event tuple + step-RNG
word block into a rolling per-lane digest and checkpoints it every
`fr_digest_every` steps. Two executions of the same (machine, config,
seed) agree on a checkpoint exactly as far as their event streams agree,
and once diverged they stay diverged (the fold is a bijective mix per
word, so re-convergence is a ~2^-64 accident). That monotonicity is what
makes the checkpoint trail *bisectable*: the first divergent checkpoint
localizes a determinism break — corpus rot, a stream-version skew, a
jax upgrade that moved threefry, a broken engine change — to one
`fr_digest_every`-step segment without storing full traces.

Corpus entries record their trail at hunt/record time
(`CorpusEntry.digests` + `digest_final` + environment `meta`);
`python -m madsim_tpu audit` replays each entry on the host replay path
(the bit-identity oracle) and reports "first divergent checkpoint at
step k: expected d₀ got d₁" — turning "the corpus rotted" from folklore
into a one-command diagnosis.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

from .core import Engine
from .corpus import entry_machine

DEFAULT_DIGEST_EVERY = 64


@dataclasses.dataclass(frozen=True)
class DigestTrail:
    """One execution's digest trail: checkpoints at exact step multiples
    of `every`, plus the final (step, digest) when the lane stopped."""

    every: int
    checkpoints: Tuple[Tuple[int, int, int], ...]  # (step, d0, d1), ascending
    final_step: int
    final: Tuple[int, int]  # (d0, d1) at the stopping step
    failed: bool
    fail_code: int

    def to_lists(self) -> Tuple[List[List[int]], List[int]]:
        """(digests, digest_final) in the corpus JSON shape."""
        return (
            [[s, d0, d1] for s, d0, d1 in self.checkpoints],
            [self.final_step, *self.final],
        )


def decode_checkpoint_ring(lane_fr) -> List[Tuple[int, int, int]]:
    """Decode one lane's checkpoint ring (LaneState.fr slice) into
    (step, d0, d1) tuples, oldest first. Slots with step < 0 are unused."""
    import numpy as np

    steps = np.asarray(lane_fr["ck_step"])
    order = np.argsort(steps, kind="stable")
    order = order[steps[order] >= 0]
    d0 = np.asarray(lane_fr["ck_d0"])
    d1 = np.asarray(lane_fr["ck_d1"])
    return [(int(steps[i]), int(d0[i]), int(d1[i])) for i in order]


def fr_variant(engine: Engine, every: int, ring: int) -> Engine:
    """An Engine identical to `engine` but with the flight recorder on at
    the given checkpoint cadence. Because the recorder is asserted
    bit-identical under its gate, the trail is a property of the
    underlying run, not of the recording."""
    cfg = dataclasses.replace(
        engine.config,
        flight_recorder=True,
        fr_digest_every=every,
        fr_digest_ring=ring,
    )
    return Engine(engine.machine, cfg, use_pallas_pop=engine.use_pallas_pop)


def trail_ring(max_steps: int, every: int) -> int:
    """Ring slots for a trail of up to `max_steps` steps: the
    `max_steps // every + 2` that never wraps, rounded up to a power of
    two, floor 8. The ring is a SHAPE of the replay program, and a
    corpus entry's `max_steps` is the step count of the seed it shrank:
    sized exactly, every find would lower a program of its own; in
    buckets, the finds of one machine share a handful. The trail does
    not see the size — a ring that never wraps holds each checkpoint
    once, and `decode_checkpoint_ring` skips the unused slots."""
    return max(8, 1 << (max_steps // every + 1).bit_length())


def collect_trail(
    engine: Engine,
    seed: int,
    max_steps: int,
    every: int = DEFAULT_DIGEST_EVERY,
) -> DigestTrail:
    """Replay one seed on the host replay path (single compiled dispatch,
    bit-identical to the device lane) with the recorder on, retaining
    EVERY checkpoint (the ring is sized past max_steps, so it never
    wraps)."""
    from .replay import replay

    eng = engine
    if (
        not engine.config.flight_recorder
        or engine.config.fr_digest_every != every
        or engine.config.fr_digest_ring * every <= max_steps
    ):
        eng = fr_variant(engine, every, trail_ring(max_steps, every))
    rp = replay(eng, seed, max_steps=max_steps, trace=False)
    fr = rp.state.fr
    return DigestTrail(
        every=every,
        checkpoints=tuple(decode_checkpoint_ring(fr)),
        final_step=int(rp.state.step),
        final=(int(fr["d0"]), int(fr["d1"])),
        failed=bool(rp.state.failed),
        fail_code=int(rp.state.fail_code),
    )


@dataclasses.dataclass(frozen=True)
class Divergence:
    """First point where a replayed trail leaves the recorded one."""

    step: int  # recorded checkpoint (or final) step that mismatched
    expected: Tuple[int, int]
    got: Optional[Tuple[int, int]]  # None: replay never reached that step
    segment: Tuple[int, int]  # (last agreeing step, first divergent step]
    at_final: bool  # divergence surfaced only at the final digest

    def __str__(self) -> str:
        got = (
            f"got {self.got[0]:#010x}:{self.got[1]:#010x}"
            if self.got is not None
            else "replay never reached that step"
        )
        where = "final digest" if self.at_final else "checkpoint"
        return (
            f"first divergent {where} at step {self.step} (segment "
            f"({self.segment[0]}, {self.segment[1]}]): expected "
            f"{self.expected[0]:#010x}:{self.expected[1]:#010x}, {got}"
        )


def first_divergence(
    recorded: Sequence[Sequence[int]],
    recorded_final: Optional[Sequence[int]],
    replayed: DigestTrail,
) -> Optional[Divergence]:
    """Binary-search the recorded checkpoint list for the first entry the
    replayed trail contradicts.

    Divergence is monotone along the trail (streams that have forked
    never re-agree), so "checkpoint i mismatches" is a sorted predicate
    and O(log n) probes suffice — the protocol stays cheap even for
    trails with thousands of checkpoints. Returns None when every
    checkpoint AND the final digest agree.
    """
    rep = {s: (d0, d1) for s, d0, d1 in replayed.checkpoints}
    rec = [(int(s), int(d0), int(d1)) for s, d0, d1 in recorded]

    def bad(i: int) -> bool:
        s, d0, d1 = rec[i]
        return rep.get(s) != (d0, d1)

    first_bad = len(rec)
    lo, hi = 0, len(rec) - 1
    while lo <= hi:
        mid = (lo + hi) // 2
        if bad(mid):
            first_bad = mid
            hi = mid - 1
        else:
            lo = mid + 1
    if first_bad < len(rec):
        s, d0, d1 = rec[first_bad]
        prev = rec[first_bad - 1][0] if first_bad else 0
        return Divergence(
            step=s,
            expected=(d0, d1),
            got=rep.get(s),
            segment=(prev, s),
            at_final=False,
        )
    if recorded_final is not None:
        fs, fd0, fd1 = (int(x) for x in recorded_final)
        if (fs, fd0, fd1) != (replayed.final_step, *replayed.final):
            prev = rec[-1][0] if rec else 0
            return Divergence(
                step=fs,
                expected=(fd0, fd1),
                got=replayed.final,
                segment=(prev, fs),
                at_final=True,
            )
    return None


def engine_meta(config) -> dict:
    """Environment fingerprint recorded next to a digest trail — when an
    audit later reports divergence, this says what the trail was
    recorded UNDER (the usual rot suspects: jax/jaxlib upgrade, python
    major, engine stream version)."""
    import platform

    import jax
    import jaxlib

    import madsim_tpu

    return {
        "madsim_tpu": getattr(madsim_tpu, "__version__", "?"),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "python": platform.python_version(),
        "rng_stream": config.rng_stream,
        "digest": "fr-v1",
    }


@dataclasses.dataclass
class AuditOutcome:
    entry: object  # CorpusEntry
    status: str  # "match" | "diverged" | "no-digests"
    divergence: Optional[Divergence]
    trail: DigestTrail
    verdict: str

    @property
    def ok(self) -> bool:
        return self.status != "diverged"


def audit_entry(entry, build_machine: Callable[[str, int], object]) -> AuditOutcome:
    """Replay one corpus entry on the host path and bisect its recorded
    digest trail. Also cross-checks the behavioral outcome (fail code)
    so a divergence report says whether the finding itself survived."""
    eng = Engine(entry_machine(entry, build_machine), entry.config)
    every = entry.digest_every or DEFAULT_DIGEST_EVERY
    trail = collect_trail(eng, entry.seed, entry.max_steps, every=every)
    behavior = (
        f"replay {'fails with code ' + str(trail.fail_code) if trail.failed else 'passes'}"
        f" at step {trail.final_step} (entry expects code {entry.fail_code})"
    )
    if not entry.digests and not entry.digest_final:
        return AuditOutcome(
            entry, "no-digests", None, trail,
            f"no recorded digests (re-record with `audit --record`); {behavior}",
        )
    div = first_divergence(entry.digests, entry.digest_final or None, trail)
    if div is None:
        return AuditOutcome(
            entry, "match", None, trail,
            f"digest trail matches ({len(entry.digests)} checkpoints); {behavior}",
        )
    return AuditOutcome(entry, "diverged", div, trail, f"{div}; {behavior}")


def record_entry(
    entry,
    build_machine: Callable[[str, int], object],
    every: int = DEFAULT_DIGEST_EVERY,
):
    """Re-record one corpus entry's digest trail + environment metadata
    at HEAD. Returns (updated_entry, trail) — the trail carries the
    behavioral outcome (failed / fail_code) so callers can check the
    entry's status contract before saving."""
    eng = Engine(entry_machine(entry, build_machine), entry.config)
    trail = collect_trail(eng, entry.seed, entry.max_steps, every=every)
    digests, final = trail.to_lists()
    new = dataclasses.replace(
        entry,
        digest_every=every,
        digests=digests,
        digest_final=final,
        # MERGE with the caller's meta rather than replacing it: the
        # fleet files entries with provenance keys (`filed_by`,
        # `repro`, `why_kinds`) that must survive re-recording; the
        # environment fingerprint wins on any key collision.
        meta={**entry.meta, **engine_meta(entry.config)},
    )
    return new, trail
