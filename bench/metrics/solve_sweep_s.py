"""solve_sweep_s (panels and sweeps, s): device seconds per call of the
ops the program runs under its sweep scope (scopes.json: the rounded
substitution sweeps of the solves, not the quire ones)."""
import scope_reduce


def read(ctx):
    return scope_reduce.per_call(ctx, scope_reduce.TABLE["solve_sweep_s"])
