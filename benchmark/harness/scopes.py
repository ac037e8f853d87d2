"""Device time by the program's own named scopes.

A trace's events carry an instruction's HLO line and no ``op_name``; the
program knows what each instruction of its compiled programs belongs to
(``raydp_tpu.obs.profiler.device_scopes()``: {program: {instruction name:
{"result", "scopes": [outermost, ..., innermost], "mixed"}}}, the scopes
being the names ``obs.device_scope`` was opened under). ``join`` puts the two
together, by instruction name and result type (two programs may both have a
``%fusion.12``), and gives device seconds by scope:

- a PARTITION by innermost scope. An operation no live program knows, or
  whose instruction carries no scope (a copy the compiler made, a loop's own
  counter), goes to ``unattributed``. One that two programs know (a step's
  and an evaluation's forward pass have instructions of one name and type,
  and a trace pools two that read the same letter for letter) belongs to
  the scopes both give where they agree on the innermost, and goes to
  ``ambiguous`` where they do not. The parts sum to the operations' summed
  time exactly: time is counted in whole picoseconds.
- MEMBERSHIP sums: ``hybridlm.experts`` is everything with that scope
  anywhere in its chain, ``.gmm`` and the rest inside.

Control-flow containers are left out, as ``xplane`` leaves them out of busy
time: their events span their bodies' operations. A JAX conditional is
``%cond.N`` (forward) or ``%conditional.N`` (transposed) in a trace, and
``xplane.CONTAINERS`` knows only the second, so ``%cond`` is left out here too.

A fusion is one event and belongs to the scopes of the instruction XLA
names it by (its root, or the product it was built around); ``mixed`` is the
time of the fusions in which some fused instruction's innermost scope is not
among them: what one name a fusion blurs.

``table(sources)`` is what the per-layer readers call: the join of
``sources["trace"].ops`` with the program's map, made once a trace and
printed once a process to stderr (scopes by seconds, with calls and the
mixed share; the Mosaic calls by kernel with the scopes they lie under; the
heaviest operations left unattributed), so that PERF.md's tables can be
copied and not composed. None where the program has no such map (a parent
commit from before it), so the readers return None and the line leaves
their metrics out."""

from __future__ import annotations

import dataclasses
import re
import sys
import time
from typing import Dict, List, Optional, Tuple

from . import xplane

CONTAINERS = xplane.CONTAINERS + ("%cond",)
UNATTRIBUTED, AMBIGUOUS = "unattributed", "ambiguous"
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_MOSAIC = 'custom_call_target="tpu_custom_call"'
_PS = 1e12


def is_container(line: str) -> bool:
    """A control-flow container's event: by its instruction's name (what a
    trace reader has always gone by) or by its operation."""
    if line.startswith(CONTAINERS):
        return True
    operation = _OPCODE.search(line.partition(" = ")[2])
    return bool(operation) and operation.group(1) in (
        "while", "conditional", "call")


def _bare(result: str) -> str:
    """A result type as a key: index comments and spaces left out."""
    return re.sub(r"/\*.*?\*/|\s", "", result)


def instruction_name(line: str) -> str:
    return line.partition(" = ")[0].strip().lstrip("%")


@dataclasses.dataclass
class Row:
    ps: int = 0
    calls: int = 0
    mixed_ps: int = 0


@dataclasses.dataclass
class Table:
    """Picoseconds throughout; ``seconds(ps)`` for display."""
    total_ps: int = 0
    parts: Dict[str, Row] = dataclasses.field(default_factory=dict)  # by innermost scope
    members: Dict[str, int] = dataclasses.field(default_factory=dict)  # scope anywhere in the chain
    unknown_ps: int = 0  # of ``unattributed``: in no live program's map at all
    operations: List[tuple] = dataclasses.field(default_factory=list)  # (line, calls, ps, part, chain)

    def member_s(self, scope: str) -> float:
        return self.members.get(scope, 0) / _PS

    def part_s(self, part: str) -> float:
        row = self.parts.get(part)
        return row.ps / _PS if row else 0.0

    def blind_share(self) -> Optional[float]:
        """(unattributed + ambiguous) over the operations' summed time."""
        if not self.total_ps:
            return None
        blind = sum(self.parts[p].ps for p in (UNATTRIBUTED, AMBIGUOUS)
                    if p in self.parts)
        return blind / self.total_ps

    def outside(self, *scopes: str) -> int:
        """Picoseconds of the operations with none of ``scopes`` in their
        chain (counted from the operations, not by subtraction)."""
        return sum(ps for _, _, ps, _, chain in self.operations
                   if not any(s in chain for s in scopes))


def join(ops: Dict[str, Tuple[int, float]],
         programs: Dict[str, Dict[str, dict]]) -> Table:
    """``ops``: a trace's {HLO line: (calls, seconds)}; ``programs``: the
    program's map. See the module's docstring."""
    known: Dict[Tuple[str, str], set] = {}
    for instructions in programs.values():
        for name, said in instructions.items():
            known.setdefault((name, _bare(said["result"])), set()).add(
                (tuple(said["scopes"]), bool(said.get("mixed"))))
    table = Table()
    for line, (calls, seconds) in ops.items():
        if is_container(line):
            continue
        ps = round(seconds * _PS)
        found = known.get(
            (instruction_name(line), _bare(xplane.result_type(line))))
        chains = {chain for chain, _ in found} if found else set()
        chain: tuple = ()
        if len({c[-1:] for c in chains}) > 1:
            part = AMBIGUOUS
        else:
            # the programs that know it agree on the innermost scope (a
            # step's and an evaluation's forward pass: the same region, in
            # and out of ``loss_and_grad``): it belongs to the scopes all
            # of them give
            chain = tuple(scope for scope in min(chains, key=len, default=())
                          if all(scope in c for c in chains))
            part = chain[-1] if chain else UNATTRIBUTED
        row = table.parts.setdefault(part, Row())
        row.ps += ps
        row.calls += calls
        if part not in (UNATTRIBUTED, AMBIGUOUS) and any(m for _, m in found):
            row.mixed_ps += ps
        if not found:
            table.unknown_ps += ps
        for scope in chain:
            table.members[scope] = table.members.get(scope, 0) + ps
        table.total_ps += ps
        table.operations.append((line, calls, ps, part, chain))
    return table


def program_scopes() -> Optional[dict]:
    """The program's own map, or None where the program has none to give."""
    try:
        from raydp_tpu.obs import profiler
    except ImportError:
        return None
    ask = getattr(profiler, "device_scopes", None)
    return ask() if callable(ask) else None


_made: list = []  # [(trace, Table | None)]: one join a trace
_printed = False


def table(sources) -> Optional[Table]:
    """The join for ``sources["trace"]``; None without a trace or a map."""
    global _printed
    trace = sources.get("trace")
    if trace is None:
        return None
    for seen, made in _made:
        if seen is trace:
            return made
    t0 = time.perf_counter()
    programs = program_scopes()
    t1 = time.perf_counter()
    made = join(trace.ops, programs) if programs else None
    _made.append((trace, made))
    if made is not None and not _printed:
        _printed = True
        print(f"the program's map of scopes took {t1 - t0:.3f} s to give (the "
              f"programs' text read and parsed, after the traced stretch), "
              f"the join {time.perf_counter() - t1:.3f} s\n"
              + render(made, programs), file=sys.stderr, flush=True)
    return made


def steps(sources) -> Optional[int]:
    """The steps a per-step reader divides by: the program's own count of
    the traced stretch where the driver gives it (the LM cells), else the
    call count most operations share (the DLRM cells)."""
    trace = sources.get("trace")
    count = sources.get("values", {}).get("steps_in_trace")
    if not count and trace is not None:
        count = xplane.steps_traced(trace.ops)
    return count or None


def member_ms_per_step(sources, *scopes: str) -> Optional[float]:
    """Device milliseconds a step under any of ``scopes`` (membership; the
    scopes must not nest in each other); None where nothing lies there."""
    made, count = table(sources), steps(sources)
    if made is None or not count:
        return None
    seconds = sum(made.member_s(scope) for scope in scopes)
    return 1e3 * seconds / count if seconds > 0 else None


def render(made: Table, programs: Optional[dict] = None) -> str:
    """The whole table as text."""
    def ms(ps):
        return f"{ps / 1e9:12.3f}"

    total = max(made.total_ps, 1)
    out = ["device time by scope (benchmark/harness/scopes.py): "
           f"{made.total_ps / _PS:.9f} s in {len(made.operations)} operations"
           + (f" joined to {len(programs)} programs "
              f"({', '.join(f'{k}: {len(v)}' for k, v in programs.items())})"
              if programs else "")]
    out.append(f"{'innermost scope':44s}{'ms':>12s}{'share %':>9s}"
               f"{'calls':>9s}{'mixed %':>9s}")
    for part, row in sorted(made.parts.items(), key=lambda kv: -kv[1].ps):
        out.append(f"{part:44s}{ms(row.ps)}{100 * row.ps / total:9.2f}"
                   f"{row.calls:9d}{100 * row.mixed_ps / max(row.ps, 1):9.1f}")
    out.append(f"{'  the parts, summed':44s}"
               f"{ms(sum(r.ps for r in made.parts.values()))}"
               f"   (the operations: {ms(made.total_ps).strip()}; of "
               f"{UNATTRIBUTED}, in no live program's map: "
               f"{ms(made.unknown_ps).strip()})")
    out.append(f"{'membership (scope anywhere in the chain)':44s}{'ms':>12s}"
               f"{'share %':>9s}")
    for scope, ps in sorted(made.members.items(), key=lambda kv: -kv[1]):
        out.append(f"{scope:44s}{ms(ps)}{100 * ps / total:9.2f}")
    halves = ("loss_and_grad", "optimizer_update")
    outside = made.outside(*halves)
    out.append(
        " + ".join(f"{h} {ms(made.members.get(h, 0)).strip()}" for h in halves)
        + f" + outside both {ms(outside).strip()} = "
        + ms(sum(made.members.get(h, 0) for h in halves) + outside).strip())
    kernels: Dict[str, Dict[str, list]] = {}
    for line, calls, ps, part, _ in made.operations:
        if _MOSAIC in line:
            kind = re.sub(r"[.\d]+$", "", instruction_name(line))
            slot = kernels.setdefault(kind, {}).setdefault(part, [0, 0])
            slot[0] += calls
            slot[1] += ps
    for kind, under in sorted(kernels.items()):
        out.append(f"Mosaic calls %{kind}: " + "; ".join(
            f"{calls} calls {ms(ps).strip()} ms under {part}"
            for part, (calls, ps) in under.items()))
    blind = sorted((op for op in made.operations
                    if op[3] in (UNATTRIBUTED, AMBIGUOUS)),
                   key=lambda op: -op[2])[:5]
    for line, calls, ps, part, _ in blind:
        out.append(f"{part}: {ms(ps).strip()} ms in {calls} calls of "
                   f"{xplane.short_name(line)}")
    return "\n".join(out)
