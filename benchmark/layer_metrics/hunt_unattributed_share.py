"""Stream driver, hunt cells: share (%) of a hunt's wall-clock (call of
`hunt` to the return of `audit`) under none of the program's spans — what
the tracing still cannot see. Median over the window's hunts; None where
the program has no `hunt_report` span (it is from before the tree)."""

from benchmark import hunt_spans, trace_reduce


def read(obs):
    def measure(spans, wall):
        if not any(s["name"] == "hunt_report" for s in spans) or wall <= 0:
            return None
        covered = trace_reduce.union((s["t0"], s["t1"]) for s in spans)
        return 100.0 * (wall - trace_reduce.total(covered)) / wall
    return hunt_spans.per_hunt(obs, measure)
