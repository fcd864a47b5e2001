"""Device seconds under program scopes that ``scopes.SCOPES`` does not
list (the expert layer's ``moe_route``, ``moe_dispatch`` and
``moe_experts``), read from the same trace and name stacks as
``scopes.py``: an op counts to a scope when the scope is a component of
its ``tf_op`` name stack, bare or inside transformations, forward and
backward alike.  Control flow is left out.  On a program without the
scope the sum is 0.

Some ops lose their name stack: XLA's TPU ragged dot (what
``jax.lax.ragged_dot`` lowers to) runs as ops of its own named
``ragged-dot-metadata`` and ``ragged-dot-none``, whose ``tf_op`` is that
name.  ``seconds`` takes such ops by the prefix of their op name."""
from __future__ import annotations

import collections

import scopes
import spec
import xtrace


def _bare(tf_op: str) -> frozenset:
    parts = tf_op.rsplit(":", 1)[0].split("/")
    return frozenset(scopes._WRAP.sub("", p) for p in parts)


def _ops(ctx) -> dict:
    """{(scopes of the op's name stack, op name): device ns}, read once
    per run (kept in ctx)."""
    if "namestack" not in ctx:
        tdir = spec.ROOT / ".bench_trace" / ctx["cell"]["name"]
        tf_ops = scopes.read_xspace(xtrace.xplane_file(str(tdir)))[0]
        out = collections.Counter()
        trace = ctx["trace"]
        for d in trace.devices:
            for name, _, du in trace.ops[d]:
                if not xtrace.CONTROL.match(name):
                    out[(_bare(tf_ops.get(name, "")),
                         xtrace.op_name(name))] += du
        ctx["namestack"] = out
    return ctx["namestack"]


def seconds(ctx, *names, op_prefixes=()) -> float:
    """Device seconds (summed over devices) of the ops under any of
    ``names``, or whose op name starts with one of ``op_prefixes``; each
    op counted once."""
    want = set(names)
    return sum(du for (cats, op), du in _ops(ctx).items()
               if cats & want or op.startswith(tuple(op_prefixes))) / 1e9
