"""Failing-seed shrinking — minimize the reproduction of a flagged seed.

The reference reproduces a failure with `MADSIM_TEST_SEED=N` and the full
original config; this module goes further and bisects the *config* down
to a minimal one that still reproduces the same failure code:

  * fewer injected faults (fault i's parameters are drawn from an
    independent key-chain position, so a plan with n_faults=f keeps the
    first f faults bit-identical — candidates are honest prefixes)
  * packet loss off (if it was on)
  * fault-KIND ablation: each enabled `allow_*` chaos flag (and
    `strict_restart`) is tried off — candidates whose honest replay
    still fails with the same code drop the kind, so the result names
    the minimal chaos vocabulary. (Turning a scheduled kind off changes
    the remaining faults' drawn parameters — that's fine: every
    candidate is verified by a full replay, never assumed.)
  * the churn process (`FaultPlan.churn`) stopped as early as still
    reproduces: a process has no list of faults to take a prefix of,
    but its ticks before `churn_until_us` do not depend on where it
    ends, so an earlier end is an honest prefix of the applied faults —
    bisected, every probe a replay
  * horizon cut to just past the failure time
  * step budget cut to just past the failing step

Every candidate is verified by an actual replay; the result reports only
transformations that kept the SAME fail code. Exposed as
`python -m madsim_tpu shrink --machine M --seed N ...`.

With `EngineConfig.provenance` (or an explicit `prov_word`), the
violation's causal-provenance word steers the candidate ORDER — the
fault-count scan jumps straight to the smallest prefix containing every
implicated fault, and the kind ablation bulk-drops the non-implicated
kinds in one candidate — cutting replays on multi-fault finds while the
verify-by-replay contract stays intact (attribution is an
over-approximation and is never trusted, only used to order guesses).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from ..kinds import FLAG_BY_KIND
from ..perf.recorder import maybe_note, maybe_span
from .core import Engine, EngineConfig
from .replay import ReplayResult, replay


# Ablation order: newest/most-exotic kinds first so the reported
# minimal set leans on the legacy vocabulary when possible. The order
# is shrink policy; the name -> FaultPlan-field pairing comes from the
# shared madsim_tpu/kinds.py table (lint rule G003 asserts this list
# covers the whole vocabulary). Each entry is (report name, field).
ABLATION_ORDER = (
    "torn", "heal-asym", "delay", "storm", "group", "dir",
    "pause", "skew", "dup", "strict-restart", "kill", "pair",
)
ABLATABLE_KINDS = tuple((name, FLAG_BY_KIND[name]) for name in ABLATION_ORDER)

# the churn stage stops bisecting `churn_until_us` at this width: a few
# ticks of the fig8 set (mean sleep ~31 ms), 5-7 replays from seconds
CHURN_SHRINK_RESOLUTION_US = 100_000


@dataclasses.dataclass
class ShrinkResult:
    seed: int
    fail_code: int
    original: EngineConfig
    shrunk: EngineConfig
    steps: int              # events to failure under the shrunk config
                            # (itself a sufficient --max-steps budget)
    fail_time_us: int
    attempts: int           # replays spent shrinking
    kinds_removed: tuple = ()  # chaos flags ablated off (honest replays)
    guided: bool = False       # provenance attribution steered the order
    prov_kinds: tuple = ()     # kinds the violation's provenance implicated

    def summary(self) -> str:
        o, s = self.original, self.shrunk
        parts = []
        if s.faults.n_faults != o.faults.n_faults:
            parts.append(f"faults {o.faults.n_faults} -> {s.faults.n_faults}")
        if s.packet_loss_rate != o.packet_loss_rate:
            parts.append(f"loss {o.packet_loss_rate} -> 0")
        if s.faults.churn_until_us != o.faults.churn_until_us:
            parts.append(
                f"churn until {o.faults.churn_until_us}us -> "
                f"{s.faults.churn_until_us}us"
            )
        if self.kinds_removed:
            parts.append("kinds -" + ",-".join(self.kinds_removed))
        if s.horizon_us != o.horizon_us:
            parts.append(f"horizon {o.horizon_us}us -> {s.horizon_us}us")
        changed = "; ".join(parts) if parts else "config already minimal"
        guided = (
            f", provenance-guided by [{','.join(self.prov_kinds)}]"
            if self.guided else ""
        )
        return (
            f"seed {self.seed} fails with code {self.fail_code} in "
            f"{self.steps} events (t={self.fail_time_us}us); {changed} "
            f"[{self.attempts} verification replays{guided}]"
        )


def _candidate(
    engine: Engine, cand_cfg: Optional[EngineConfig], seed: int,
    max_steps: int, code: Optional[int], stage: str,
) -> Optional[ReplayResult]:
    """One verification replay under `cand_cfg` (None: the engine as it
    is), the candidate `Engine`'s construction included in its span.
    Returns the replay when it fails with `code` (any code when None)."""
    with maybe_span("shrink_candidate", stage=stage):
        eng = engine if cand_cfg is None else Engine(engine.machine, cand_cfg)
        rp = replay(eng, seed, max_steps=max_steps, trace=False)
        accepted = rp.failed and code in (None, rp.fail_code)
        maybe_note(accepted=bool(accepted))
    return rp if accepted else None


def shrink(
    engine: Engine,
    seed: int,
    max_steps: int = 10_000,
    prov_word: Optional[int] = None,
) -> ShrinkResult:
    """Minimize the failing configuration for `seed`.

    With a violation provenance word (`prov_word`, or for free from the
    base replay when `engine.config.provenance` is on), attribution
    steers the candidate ORDER: the fault-count scan first tries the
    smallest prefix that still contains every implicated fault, and the
    kind ablation first tries every NON-implicated kind off in one bulk
    candidate — cutting the replay count on multi-fault finds. Guidance
    never weakens the contract: every accepted candidate is still
    verified by a full honest replay reproducing the same fail code
    (attribution over-approximates, so a guided guess can fail — the
    scan then falls back to the unguided order).

    Raises ValueError if the seed does not fail under the given engine.
    """
    base = _candidate(engine, None, seed, max_steps, None, "base")
    if base is None:
        raise ValueError(
            f"seed {seed} does not fail under this config (within "
            f"{max_steps} steps) — nothing to shrink"
        )
    code = base.fail_code
    attempts = 1
    cfg = engine.config
    best = base

    # provenance attribution (when available): implicated fault indices
    # + kind names — the candidate-ordering hints
    if prov_word is None and engine.config.provenance:
        prov_word = int(base.state.fail_prov)
    att = None
    if prov_word:
        from .provenance import implicated

        att = implicated(engine, seed, int(prov_word))
    guided = att is not None
    imp_kinds = set(att.kinds) if att else set()

    # 1. fewest faults whose prefix-plan still reproduces (linear scan from
    #    zero: the minimal candidate first). Guided: a prefix can only
    #    reproduce if it contains the implicated faults, so try the
    #    smallest such prefix FIRST — on a hit that is ONE replay where
    #    the unguided scan pays max(implicated)+2; on a miss (attribution
    #    over-approximated nothing away) fall back to the full scan.
    def try_n_faults(f: int):
        cand_cfg = dataclasses.replace(
            cfg, faults=dataclasses.replace(cfg.faults, n_faults=f)
        )
        rp = _candidate(engine, cand_cfg, seed, max_steps, code, "faults")
        return cand_cfg, rp

    guessed = False
    tried_guess = None
    if att and att.faults and not att.aliased:
        guess = max(f.index for f in att.faults) + 1
        if guess < cfg.faults.n_faults:
            attempts += 1
            tried_guess = guess
            cand_cfg, rp = try_n_faults(guess)
            if rp is not None:
                cfg, best = cand_cfg, rp
                guessed = True
    if not guessed:
        for f in range(cfg.faults.n_faults):
            if f == tried_guess:
                continue  # already replayed above
            attempts += 1
            cand_cfg, rp = try_n_faults(f)
            if rp is not None:
                cfg, best = cand_cfg, rp
                break

    # 2. packet loss off
    if cfg.packet_loss_rate > 0:
        cand_cfg = dataclasses.replace(cfg, packet_loss_rate=0.0)
        attempts += 1
        rp = _candidate(engine, cand_cfg, seed, max_steps, code, "loss")
        if rp is not None:
            cfg, best = cand_cfg, rp

    # 3. fault-kind ablation: try each enabled chaos flag off. Honest —
    #    every candidate is a full replay required to reproduce the SAME
    #    fail code; flags whose removal changes the outcome stay. A
    #    scheduled plan must keep at least one kind (the constructor
    #    rejects an empty vocabulary with n_faults > 0).
    #    Guided: attribution names the implicated kinds, so first try
    #    every NON-implicated kind off in ONE bulk candidate — on a hit
    #    the per-kind scan then only visits the implicated kinds
    #    (1 + |implicated| replays instead of |enabled|).
    kinds_removed = []
    enabled = [
        (name, field)
        for name, field in ABLATABLE_KINDS
        if getattr(cfg.faults, field)
    ]
    scan = enabled
    if guided:
        non_imp = [(n, f) for n, f in enabled if n not in imp_kinds]
        if len(non_imp) >= 2:
            bulk_faults = dataclasses.replace(
                cfg.faults, **{f: False for _n, f in non_imp}
            )
            if bulk_faults.n_faults == 0 or bulk_faults.enabled_kinds():
                cand_cfg = dataclasses.replace(cfg, faults=bulk_faults)
                attempts += 1
                rp = _candidate(
                    engine, cand_cfg, seed, max_steps, code, "kinds"
                )
                if rp is not None:
                    cfg, best = cand_cfg, rp
                    kinds_removed.extend(n for n, _f in non_imp)
                    # only the implicated kinds are left to try
                    scan = [(n, f) for n, f in enabled if n in imp_kinds]
    for kind_name, field in scan:
        if not getattr(cfg.faults, field):
            continue
        cand_faults = dataclasses.replace(cfg.faults, **{field: False})
        if cand_faults.n_faults > 0 and not cand_faults.enabled_kinds():
            continue
        cand_cfg = dataclasses.replace(cfg, faults=cand_faults)
        attempts += 1
        rp = _candidate(engine, cand_cfg, seed, max_steps, code, "kinds")
        if rp is not None:
            cfg, best = cand_cfg, rp
            kinds_removed.append(kind_name)

    # 3b. the churn process, ended as early as still reproduces. Ending
    #     it just past the failure is sound by construction (the heal
    #     lands after the failing event); below that, bisect: an end at
    #     `mid` keeps every tick before `mid` as it was and heals there
    #     (kind kv3a: heals, kills the named nodes and restarts them —
    #     the same bisection; a find that needs a split standing, as
    #     `demo-localget-kvraft`'s, keeps the end just past the failure).
    #     Not monotone in general (a heal can also CAUSE the failing
    #     interleaving), so the bisection is a search order, and only
    #     replays that reproduce the code move `hi`.
    if cfg.faults.churn is not None:

        def try_until(until_us: int):
            cand_cfg = dataclasses.replace(
                cfg,
                faults=dataclasses.replace(cfg.faults, churn_until_us=until_us),
            )
            return cand_cfg, _candidate(
                engine, cand_cfg, seed, max_steps, code, "churn"
            )

        hi = cfg.faults.churn_until_us
        first = min(hi, int(best.state.now_us) + 1)
        lo = 0
        if first < hi:
            attempts += 1
            cand_cfg, rp = try_until(first)
            if rp is not None:
                cfg, best, hi = cand_cfg, rp, first
        while hi - lo > CHURN_SHRINK_RESOLUTION_US:
            mid = (lo + hi) // 2
            attempts += 1
            cand_cfg, rp = try_until(mid)
            if rp is not None:
                cfg, best, hi = cand_cfg, rp, mid
            else:
                lo = mid

    # 4. horizon just past the failure (sound by construction — events at
    #    t < horizon are unaffected by the horizon value — but verified)
    fail_t = int(best.state.now_us)
    if fail_t + 1 < cfg.horizon_us:
        cand_cfg = dataclasses.replace(cfg, horizon_us=fail_t + 1)
        attempts += 1
        rp = _candidate(engine, cand_cfg, seed, max_steps, code, "horizon")
        if rp is not None:
            cfg, best = cand_cfg, rp

    # 5. the exact failing step count is itself a sufficient step budget
    steps = int(best.state.step)
    return ShrinkResult(
        seed=seed,
        fail_code=code,
        original=engine.config,
        shrunk=cfg,
        steps=steps,
        fail_time_us=int(best.state.now_us),
        attempts=attempts,
        kinds_removed=tuple(kinds_removed),
        guided=guided,
        prov_kinds=tuple(att.kinds) if att else (),
    )
