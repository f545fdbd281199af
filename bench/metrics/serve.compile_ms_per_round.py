"""JAX tracing, lowering and compiling inside the window, per serving
round, in milliseconds: the program's ``jax.trace``, ``jax.lower`` and
``jax.compile`` spans over the rounds.  A window that compiles nothing
reads 0; a program that opens no ``serve.stack`` span (and so records
no compile spans either) reads nothing."""

NAMES = ("jax.trace", "jax.lower", "jax.compile")


def read(obs):
    rounds = obs.counters.get("rounds", 0)
    if not rounds or not any(s["name"] == "serve.stack" for s in obs.spans):
        return None
    return sum(obs.span_s(n) for n in NAMES) / rounds * 1e3
