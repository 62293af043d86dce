"""Retry-with-backoff for device dispatches and blocking device reads.

`Engine.run_stream` wraps every dispatch, counters poll and ring drain
in `retry_transient`: a transient backend error is retried a bounded
number of times and then fails loud; anything else propagates at once.
"""

from __future__ import annotations

import time

# Error-text markers that indicate a TRANSIENT backend failure — one a
# retry can outlive, not a programming error.
# Deliberately narrow: RESOURCE_EXHAUSTED (OOM), INVALID_ARGUMENT and
# "donated buffer" errors are NOT here — retrying those either repeats
# the failure or replays a dispatch whose donated inputs are gone.
_TRANSIENT_MARKERS = (
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "ABORTED",
    "connection reset",
    "Connection reset",
    "socket closed",
    "Socket closed",
    "backend unavailable",
)

# Dispatch retry budget (see retry_transient): attempts includes the
# first try, so 3 means "one try + two retries".
DISPATCH_RETRY_ATTEMPTS = 3
DISPATCH_RETRY_BACKOFF_S = 0.25


def is_transient_backend_error(exc: BaseException) -> bool:
    """Heuristic: does this exception's text look like a transient
    accelerator-backend failure?"""
    text = f"{type(exc).__name__}: {exc}"
    return any(marker in text for marker in _TRANSIENT_MARKERS)


def retry_transient(
    fn,
    attempts: int = DISPATCH_RETRY_ATTEMPTS,
    base_backoff_s: float = DISPATCH_RETRY_BACKOFF_S,
    sleep=time.sleep,
    on_retry=None,
    what: str = "device dispatch",
):
    """Call `fn()`; on a TRANSIENT backend error retry with exponential
    backoff up to `attempts` total tries, then fail loud (RuntimeError
    naming the attempt count, chained to the last error). Non-transient
    errors propagate immediately — in particular a dispatch whose
    donated buffers were already consumed raises jax's "donated buffer
    was deleted" error, which is deliberately not retried (the carry it
    needs no longer exists; the stream must abort, not corrupt).

    `on_retry(attempt, exc, delay_s)` fires before each backoff sleep —
    run_stream uses it to count stats["dispatch_retries"] and log.
    """
    if attempts < 1:
        raise ValueError("retry_transient needs attempts >= 1")
    last: BaseException | None = None
    for attempt in range(1, attempts + 1):
        try:
            return fn()
        except Exception as exc:  # noqa: BLE001 — filtered just below
            if not is_transient_backend_error(exc):
                raise
            last = exc
            if attempt < attempts:
                delay = base_backoff_s * (2 ** (attempt - 1))
                if on_retry is not None:
                    on_retry(attempt, exc, delay)
                sleep(delay)
    raise RuntimeError(
        f"{what} failed after {attempts} attempts on transient backend "
        f"errors (last: {last})"
    ) from last
