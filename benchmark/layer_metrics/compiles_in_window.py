"""Compile + cache: compile requests jax made inside the window (trace +
lower + compile or read from the persistent cache; jax's
`backend_compile_duration` events). 0 expected in a sweep."""


def read(obs):
    return len(obs.session.compiles_between(*obs.window))
