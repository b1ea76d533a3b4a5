"""Device time under the program's `jax.named_scope`s, from a trace.

The TPU trace names a device event by its HLO instruction and carries no
framework metadata (trace_reduce.py), but the compiled text of the same
executable does: every instruction's `metadata={op_name="jit(step)/.../
lm/moe/experts/..."}` holds the name stack it was traced under, through
`jvp`, `transpose` and `checkpoint` alike. Joining the two by instruction
name gives each event its scope (PERF.md section 7's recipe, for the
`lm_train_steps` runner alone).

`instruction_scopes` reads the text, `scope_seconds` sums leaf events of
the traced window by the first scope of `scopes` whose name appears in
the instruction's `op_name`. A fusion carries its root's metadata, so an
elementwise op fused into a neighbour's product counts with the
neighbour. Events the text does not name, or names without one of the
scopes, are `unattributed`.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Iterable, Sequence

from benchmarks import trace_reduce

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")


def instruction_scopes(compiled_text: str) -> Dict[str, str]:
    """instruction name -> op_name, for every instruction that has one."""
    out = {}
    for line in compiled_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def scope_seconds(devices: Sequence[dict], window, op_names: Dict[str, str],
                  scopes: Iterable[str], aliases: Dict[str, str] = None,
                  top: int = 8) -> dict:
    """Seconds per chip inside `window` under each of `scopes` (summed
    leaf-event durations, mean over the chips), plus `unattributed`,
    `leaf_total` and `unattributed_top` (the `top` unattributed ops,
    `[label <op_name's tail>, seconds]`). `aliases` maps a needle in an
    `op_name` to a scope, for ops whose metadata the compiler rewrote
    (XLA names its grouped-product kernels `ragged-dot-*` and drops the
    name stack). `devices` is `trace_reduce.load_xplane`'s list."""
    scopes = list(scopes)
    aliases = aliases or {}
    acc = collections.defaultdict(float)
    loose = collections.defaultdict(float)
    for dev in devices:
        for label, a, b in trace_reduce._clip(dev["ops"], window):
            if trace_reduce.is_container(label):
                continue
            name = label.split(" ", 1)[0].lstrip("%")
            op_name = op_names.get(name, "")
            scope = next((s for s in scopes if s in op_name), None)
            if scope is None:
                scope = next((s for needle, s in aliases.items()
                              if needle in op_name), "unattributed")
            acc[scope] += b - a
            acc["leaf_total"] += b - a
            if scope == "unattributed":
                loose[f"{label} <{op_name[-60:]}>"] += b - a
    n = max(len(devices), 1)
    out = {k: acc.get(k, 0.0) / n / 1e9
           for k in scopes + ["unattributed", "leaf_total"]}
    out["unattributed_top"] = [
        [k, loose[k] / n / 1e9]
        for k in sorted(loose, key=loose.get, reverse=True)[:top]]
    return out
