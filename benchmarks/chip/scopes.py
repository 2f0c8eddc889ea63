"""Map the compiled train step's instructions to the phases it names.

The program puts `jax.named_scope("client_grads" | "wire" |
"server_update")` around its phases (`launch/steps.py`). The scopes reach
the `op_name` metadata of every instruction of the compiled program; a
fusion carries its root's. The profiler names a device operation after its
instruction, so the compiled step's HLO text (`compiled.as_text()`) says
which phase each operation of a trace ran for.

Classes, by the first of the three scopes on the instruction's path:

- `client_grads/remat`: the forward recomputed under `jax.checkpoint`
  (`rematted_computation` on the path);
- `client_grads/backward`: the rest of the transposed pass (`transpose(`);
- `client_grads/forward`: the rest of `client_grads`;
- `wire`, `server_update`;
- `other`: no scope on the path.

An instruction the compiler added without an `op_name` that is a path of
the program (layout copies, bitcasts, the tuples and copies around a
`while`; a parameter's relayout carries the argument's name, such as
`state.params['lm_head']`) takes the class of the first instruction that
reads it; failing that, of the first it reads;
failing that, of the instruction that runs its computation (a `while`
body's copies go with the `while`).
"""
from __future__ import annotations

import re

from xtrace import CONTROL

SCOPES = ("client_grads", "wire", "server_update")
OTHER = "other"
# `%fusion.12 = bf16[8]{0} fusion(%p), kind=kLoop, ..., metadata={...}`;
# the opcode is the first lower-case word before a parenthesis (layouts'
# `T(8,128)` tiles start upper-case)
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"(?<![\w.])([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_REF = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")


def classify(op_name: str) -> str:
    parts = re.split(r"[/()]", op_name)
    scope = next((p for p in parts if p in SCOPES), None)
    if scope is None:
        return OTHER
    if scope != "client_grads":
        return scope
    if "rematted_computation" in parts:
        return "client_grads/remat"
    if "transpose" in parts:
        return "client_grads/backward"
    return "client_grads/forward"


def instructions(hlo_text: str):
    """(computation, instruction, opcode, op_name, refs) for every
    instruction of every computation, in the text's order; the entry
    computation is named `ENTRY`; `refs` are the `%names` the line
    mentions after the `=` (operands and called computations)."""
    comp = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = "ENTRY" if head.group(1) else head.group(2)
            continue
        hit = _INSTR.match(line)
        if hit is None or comp is None:
            continue
        rest = hit.group(2)
        op = _OPCODE.search(rest)
        name = _OP_NAME.search(rest)
        yield (comp, hit.group(1), op.group(1) if op else "",
               name.group(1) if name else "", _REF.findall(rest))


def op_scopes(hlo_text: str) -> dict[str, str]:
    """{instruction name: class} over the whole module. Instruction names
    are unique in a module, so the entry's, the `while` bodies' and the
    fused computations' instructions share one map."""
    rows = list(instructions(hlo_text))
    comp_of = {name: comp for comp, name, _, _, _ in rows}
    got = {name: classify(op_name) for _, name, _, op_name, _ in rows
           if "/" in op_name}
    users, operands, caller = {}, {}, {}
    for comp, name, _, _, refs in rows:
        for r in refs:
            if comp_of.get(r) == comp:
                operands.setdefault(name, []).append(r)
                users.setdefault(r, []).append(name)
            elif r not in comp_of:
                caller.setdefault(r, name)  # r names a computation
    # users follow their operands in the text, so a backward sweep settles
    # chains of users and a forward one chains of operands
    for links, order in ((users, rows[::-1]), (operands, rows)):
        for _, name, _, _, _ in order:
            if name not in got:
                known = [got[x] for x in links.get(name, ())
                         if got.get(x, OTHER) != OTHER]
                if known:
                    got[name] = known[0]
    # a computation is printed before its callers: backwards, callers first
    for comp, name, _, _, _ in rows[::-1]:
        if name not in got:
            got[name] = got.get(caller.get(comp), OTHER)
    return got


def step_hlo(tr, rows, rkey) -> str:
    """The HLO text of the harness's step (`cell.build`'s `tr`) as the
    window calls it: the state as its shardings fix it, a batch like the
    device array `rows`, the round key. Once the window's shapes have
    compiled, this is a cache hit."""
    import jax

    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tr.abstract, tr.shardings)
    batch = {"tokens": jax.ShapeDtypeStruct(rows.shape, rows.dtype,
                                            sharding=rows.sharding)}
    return tr.jitted.lower(state, batch, rkey).compile().as_text()


def device_ms(record: dict, trace: dict, want) -> float | None:
    """Device time per round, in ms, of the trace's operations whose class
    satisfies `want`; on several chips, the slowest. Control-flow
    operations contain the ones they run and are left out. None when the
    run recorded no map, or the program names none of its phases."""
    scopes = record.get("op_scopes")
    if not scopes or all(c == OTHER for c in scopes.values()):
        return None
    per_chip = [sum(v for k, v in c["op_s"].items()
                    if not k.startswith(CONTROL)
                    and want(scopes.get(k, OTHER)))
                for c in trace["chips"]]
    return 1e3 * max(per_chip) / record["rounds"]
