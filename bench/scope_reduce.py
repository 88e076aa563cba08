"""Device time by program scope: the layer names the program gives its
ops itself.

The program runs each layer of its main path under one
``jax.named_scope`` (``posit.panel``, ``posit.swap``, ...; the table is
``metrics/scopes.json``), which becomes a component of the op metadata
name of every op the layer emits:
'jit(_rgetrf_jit)/posit.swap/while/body/dynamic_slice'.  An op belongs
to the innermost scope of the table in its metadata.  An op whose
metadata names none (XLA drops the metadata of many fused loop ops)
takes the scope of the loop op it is nested in, the rule
``trace_reduce.attributed`` applies to frames.  What is left is under no
scope: copies XLA inserts with no metadata outside any loop, and work
outside every layer.
"""
from __future__ import annotations

import functools
import json
import re
from pathlib import Path

import trace_reduce

TABLE = json.loads(
    (Path(__file__).parent / "metrics" / "scopes.json").read_text())
SCOPES = frozenset(TABLE["scopes"])
_PART = re.compile(r"[^/()]+")


@functools.lru_cache(maxsize=None)
def scope_of(op_name: str, scopes: frozenset = SCOPES) -> str | None:
    """The innermost scope of ``scopes`` in an op's metadata name."""
    for part in reversed(_PART.findall(op_name or "")):
        if part in scopes:
            return part
    return None


def scoped(trace: dict, scopes: frozenset = SCOPES):
    """(op, own ns, scope) per op, with the loop's scope for an op that
    names none."""
    own, parent = trace_reduce.nesting(trace["ops"])
    got: list = []
    for i, op in enumerate(trace["ops"]):
        s = scope_of(op[4], scopes)
        if s is None and parent[i] >= 0:
            s = got[parent[i]]
        got.append(s)
    return zip(trace["ops"], own, got)


def seconds_by_scope(trace: dict) -> dict | None:
    """Device seconds per scope of the table, the ops under none under
    None; None for a trace in which no op names a scope (a program
    without scopes)."""
    out: dict = {}
    for _, own, s in scoped(trace):
        out[s] = out.get(s, 0.0) + own / 1e9
    return out if set(out) - {None} else None


def per_call(ctx: dict, scope: str | None) -> float | None:
    """Device seconds per traced call under ``scope`` (None: under no
    scope); None where the program has no scopes.  The readers share one
    reduction of the trace, kept in ``ctx``."""
    if "seconds_by_scope" not in ctx:
        ctx["seconds_by_scope"] = seconds_by_scope(ctx["trace"])
    secs = ctx["seconds_by_scope"]
    if secs is None:
        return None
    return secs.get(scope, 0.0) / ctx["calls"]
