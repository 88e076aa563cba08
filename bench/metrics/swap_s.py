"""swap_s (row swaps, s): device seconds per call of the ops the program
runs under its row-swap scope (scopes.json: the LU's row swaps on the
blocks beside each panel and their write, the solve's pivot scan)."""
import scope_reduce


def read(ctx):
    return scope_reduce.per_call(ctx, scope_reduce.TABLE["swap_s"])
