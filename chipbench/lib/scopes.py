"""The program's own names in a reduced trace: which scope each device op
ran in, and which host span each idle interval fell in.

Scopes: the model and the optimizer run under ``jax.named_scope``s
(``SCOPES``), which XLA keeps as each instruction's ``metadata={op_name=...}``
in the compiled program.  The profiler's device events carry only the HLO op
name, so the op name is mapped to its scope through the compiled text of
the program that ran it.  Host spans: ``Trainer.run`` wraps each step in a
``trainer.step`` span and its parts in ``trainer.batch``,
``trainer.dispatch`` and ``trainer.sync`` (``jax.profiler`` annotations, on
the trace's clock).  Compiles: ``repro.obs.metrics.watch_compiles`` leaves
a mark on the host timeline at each increment of its counters.

Everything below but :func:`loaded_programs` works on plain event lists and
text, so the tests can feed it events and HLO lines made by hand.  On a
program without these names each reader returns None.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, Iterable, List, Mapping, Optional, Tuple

from chipbench.lib import trace

SCOPES = ("embed", "attention", "mlp", "head", "optimizer")
OTHER, IDLE = "other", "idle"
STEP = "trainer.step"
COMPILES = "jax.backend_compiles"

# one instruction of a compiled HLO module, with its op_name metadata
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")
_MODULE_ID = re.compile(r"\(\d+\)$")


def scope_of(op_path: str) -> Optional[str]:
    """The innermost of ``SCOPES`` on an op's name path, with the
    transformations' wrappers taken off: ``transpose(jvp(attention))`` is
    ``attention``; a remat's ``checkpoint`` and ``rematted_computation``
    steps match nothing."""
    for part in reversed(op_path.split("/")):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        if part in SCOPES:
            return part
    return None


def op_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """Op name -> scope (None where none) of each instruction in a compiled
    module's text that has op_name metadata."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = scope_of(m.group(2))
    return out


def module_name(event_name: str) -> str:
    """``jit_train_step(3019...)``, as the trace names a program's run, ->
    ``jit_train_step``, as its compiled text names it."""
    return _MODULE_ID.sub("", event_name)


def scope_maps(texts: Iterable[str]) -> Dict[str, Dict[str, Optional[str]]]:
    """Module name -> op name -> scope, from compiled module texts.  Where
    two programs share a name, only the ops that they scope alike are
    kept."""
    maps: Dict[str, Dict[str, Optional[str]]] = {}
    for text in texts:
        if not text.startswith("HloModule "):
            continue
        name = text.split(None, 2)[1].rstrip(",")
        ops = op_scopes(text)
        if name in maps:
            ops = {k: v for k, v in ops.items() if maps[name].get(k) == v}
        maps[name] = ops
    return maps


def loaded_programs() -> List[str]:
    """The compiled text of every program this process still holds loaded:
    the step program among them while the cell's entry lives."""
    import gc

    from jax._src.interpreters import pxla
    texts = []
    for obj in gc.get_objects():
        if isinstance(obj, pxla.MeshExecutable):
            try:
                texts.append(obj.as_text())
            except NotImplementedError:    # the backend keeps no text
                continue
    return texts


def _module_of(ops: List[trace.Event],
               runs: List[trace.Event]) -> List[Optional[str]]:
    """For each op (sorted by start), the program whose run holds its
    start."""
    runs = sorted(runs, key=lambda r: r[1])
    out, j = [], 0
    for _, s, _ in ops:
        while j < len(runs) and runs[j][2] <= s:
            j += 1
        inside = j < len(runs) and runs[j][1] <= s
        out.append(module_name(runs[j][0]) if inside else None)
    return out


def shares(tr: trace.Trace, lo: float, hi: float,
           maps: Mapping[str, Mapping[str, Optional[str]]]
           ) -> Dict[str, float]:
    """Share of the window, averaged over the devices, in which a leaf op
    of each scope ran (the union of those ops' intervals); ``other`` for
    the leaf ops of no scope and the time a loop holds the device between
    its ops; ``idle`` for no op at all.  The shares sum to 1."""
    got: Dict[str, float] = collections.Counter()
    for plane, ops in tr.ops.items():
        lv = trace.leaves(ops)
        mods = _module_of(lv, tr.modules.get(plane, []))
        by_scope = collections.defaultdict(list)
        for (name, s, e), mod in zip(lv, mods):
            scope = maps.get(mod, {}).get(name) if mod else None
            by_scope[scope or OTHER].append((s, e))
        busy = trace.merge(((s, e) for _, s, e in ops), lo, hi)
        for scope, ivs in by_scope.items():
            got[scope] += trace.covered(ivs, lo, hi)
        got[OTHER] += trace.subtract(busy, trace.merge(
            ((s, e) for _, s, e in lv), lo, hi))
        got[IDLE] += (hi - lo) - sum(e - s for s, e in busy)
    n = max(len(tr.ops), 1)
    return {k: got[k] / n / (hi - lo) for k in (*SCOPES, OTHER, IDLE)}


def scope_frac(ctx, scope: str) -> Optional[float]:
    """``<scope>_frac`` of a traced run; None where no op of the window
    maps to any scope (a program without the scopes).  The reduction is
    made once per run and kept on ``ctx``."""
    if not hasattr(ctx, "scope_shares"):
        got = shares(ctx.trace, ctx.lo, ctx.hi,
                     scope_maps(loaded_programs()))
        ctx.scope_shares = got if any(got[s] for s in SCOPES) else None
    return ctx.scope_shares[scope] if ctx.scope_shares else None


def spans(tr: trace.Trace, name: str, lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """The host spans called ``name`` that overlap the window."""
    return [(s, e) for _, n, s, e in tr.host if n == name and s < hi
            and e > lo]


def wait_ms(tr: trace.Trace, lo: float, hi: float,
            name: str) -> Optional[float]:
    """Device idle time inside the host spans called ``name``, averaged
    over the devices, per ``trainer.step`` span of the window (ms); None
    where the window has no step span or no device."""
    steps = spans(tr, STEP, lo, hi)
    if not steps or not tr.ops:
        return None
    inside = trace.merge(spans(tr, name, lo, hi), lo, hi)
    idle = [trace.subtract(inside, trace.merge(
        ((s, e) for _, s, e in ops), lo, hi)) for ops in tr.ops.values()]
    return sum(idle) / len(idle) / len(steps) / 1e6


def marks(tr: trace.Trace, lo: float, hi: float, name: str) -> int:
    """How many host events called ``name`` start inside the window."""
    return sum(1 for _, n, s, _ in tr.host if n == name and lo <= s <= hi)
