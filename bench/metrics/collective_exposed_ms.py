"""Milliseconds per request, averaged over the chips used, in which a
collective op runs on a device and no other op does: the combine of the
row-sharded solve that compute leaves exposed.  A collective is an HLO
``all-reduce`` (or its ``-start``/``-done`` halves), read from the op's
text in the trace.  A ``while``, ``conditional`` or ``call`` op spans the
ops of its body, so it counts as neither: exposed time is the union of the
collectives and the other ops less the union of the other ops.  Requests
are those of the window's ``engine.slab`` spans that name a ``plan``
(sharded slabs).  None on a trace without collectives, or where no slab
names a plan.
"""

import re

from program_spans import window_records

#: The opcode of an HLO instruction text follows its result type and opens
#: its operand list; an operand that names an op is not followed by "(".
_COLLECTIVE = re.compile(r" all-reduce(-start|-done)?\(")
_CONTAINER = re.compile(r" (while|conditional|call)\(")


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.hi <= ctx.lo:
        return None
    recs = window_records(ctx)
    if recs is None:
        return None
    requests = sum(r.attrs.get("requests", 0) for r in recs
                   if r.name == "engine.slab" and "plan" in r.attrs)
    if requests == 0:
        return None
    exposed, seen = [], False
    for dev in ctx.devices_used():
        ops = [op for op in dev.ops if not _CONTAINER.search(op[2])]
        other = [op for op in ops if not _COLLECTIVE.search(op[2])]
        seen = seen or len(other) < len(ops)
        exposed.append(ctx.tr.length(ctx.tr.union(ops, ctx.lo, ctx.hi))
                       - ctx.tr.length(ctx.tr.union(other, ctx.lo, ctx.hi)))
    if not seen:
        return None
    return sum(exposed) / len(exposed) / 1e6 / requests
