"""T-rule executor fixture: a miniature run_stream with the same
idioms the real executor uses — jitted-with-donation factory, tuple
unpack, dispatch wrapper, sanitizer-wrapped polls — plus one of each
hazard. Expected lines are tagged `T00x expected` and discovered by
tests/test_lint_v2.py; the `clean` entrypoint must produce nothing."""

import jax
import jax.numpy as jnp
import numpy as np


def _retry(fn, *args):
    return fn(*args)


class MiniEngine:
    def _stream_fns(self, donate):
        def init_carry(seeds):
            return seeds * jnp.uint32(2)

        def segment(carry):
            return carry + jnp.uint32(1)

        donate_kw = {"donate_argnums": (0,)} if donate else {}
        fns = (jax.jit(init_carry), jax.jit(segment, **donate_kw))
        return fns

    def run_clean(self, n):
        """The honest executor: async dispatches in the loop, one
        designed device_get sync after it."""
        init_carry, segment = self._stream_fns(True)
        seeds = jnp.arange(n, dtype=jnp.uint32)
        carry = _retry(init_carry, seeds)
        for _ in range(3):
            carry = _retry(segment, carry)
        counters = np.asarray(_retry(jax.device_get, carry))
        return int(counters[0])

    def run_item_sink(self, n):
        init_carry, segment = self._stream_fns(True)
        carry = init_carry(jnp.arange(n, dtype=jnp.uint32))
        while True:
            carry = _retry(segment, carry)
            done = carry[0].item()  # T001 expected
            if done >= n:
                return done

    def run_truthy_sink(self, n):
        init_carry, segment = self._stream_fns(True)
        carry = init_carry(jnp.arange(n, dtype=jnp.uint32))
        if carry[0]:  # T001 expected
            return 1
        return 0

    def run_hidden_fetch(self, n):
        init_carry, segment = self._stream_fns(True)
        carry = init_carry(jnp.arange(n, dtype=jnp.uint32))
        done = 0
        while done < n:
            carry = _retry(segment, carry)
            snap = jax.device_get(carry)  # T002 expected
            done = int(np.asarray(snap)[0])
        return done

    def run_use_after_donate(self, n):
        init_carry, segment = self._stream_fns(True)
        carry = init_carry(jnp.arange(n, dtype=jnp.uint32))
        advanced = _retry(segment, carry)  # donates `carry`...
        stale = carry + jnp.uint32(1)  # T003 expected
        return advanced, stale

    def _stream_quartet(self, donate):
        """A factory's return passed through whole: the same slots
        donate (the real executor's `_stream_quartet`)."""
        return self._stream_fns(donate)

    def run_passthrough_use_after_donate(self, n):
        init_carry, segment = self._stream_quartet(True)
        carry = init_carry(jnp.arange(n, dtype=jnp.uint32))
        advanced = _retry(segment, carry)  # donates `carry`...
        stale = carry + jnp.uint32(1)  # T003 expected
        return advanced, stale
