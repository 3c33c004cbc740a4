"""Compile events (trace, lowering, backend compile) seen through
jax.monitoring inside the window; a new plan signature would show here."""


def read(run):
    c = run.get("counters") or {}
    return c.get("compiles_in_window")
