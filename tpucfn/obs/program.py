"""What a compiled program is made of, in the program's own words.

A device trace names an event by the instruction the compiler made of it
(``%fusion.427``), and the compiler numbers its fusions anew on every
change.  The executable's optimized HLO names the same instructions and
carries, on each, the ``op_name`` JAX and Flax wrote while tracing:
``jit(_step_fn)/transpose(jvp(Llama))/while/body/closed_call/checkpoint/
rematted_computation/layers/mlp/down_proj/dot_general``.  ``scope_map`` reads
that text once and gives every instruction a trace can show a class
``[pass, scope, root, product]``:

- ``pass``: ``forward`` (under ``jvp(``), ``backward`` (under
  ``transpose(jvp(``), ``remat`` (backward, inside a
  ``rematted_computation``), ``optimizer`` (inside the step, under no
  ``jvp``: the update, and whatever else the step does outside its loss) or
  ``none`` (no metadata, its own or borrowed: see ``_Computation.op_names``);
- ``scope``: the module path with JAX's own wrappers and the primitive's
  name taken out (``layers/mlp/down_proj``; empty for what a scan does
  between its layers);
- ``root``: the instruction's opcode, or for a fusion that of its fused
  computation's root, seen through ``bitcast`` and ``tuple``;
- ``product``: the instruction is, or its fused computation holds, a ``dot``
  or a ``convolution``.

``record_program`` writes the ``step_program`` span with that map, the
program's planned memory and where its compile came from; the benchmark's
``scope_time_share`` joins it with a device trace by instruction name.
``CacheVerdict`` listens to JAX's own cache events around one compile.
"""

from __future__ import annotations

import re
import threading
import time

PASSES = ("forward", "remat", "backward", "optimizer", "none")

# The components of an op_name that are JAX's and not a module's.  A
# transform is written around the outermost scope inside it
# (``transpose(jvp(Llama))/layers/mlp``, ``jvp(lm_head)/while/body``): the
# scope is kept and the transform taken off; a ``jit(...)`` is a function's
# boundary (``jit(_step_fn)``, ``jit(silu)``) and goes whole.
_WRAPPERS = frozenset((
    "while", "body", "cond", "closed_call", "checkpoint",
    "rematted_computation", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_jvp_call", "core_call", "remat", "pjit"))
_TRANSFORM = re.compile(r"^(\w+)\((.*)\)$")
_BRANCH = re.compile(r"^branch_\d+_fun$")

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(
    r"\b(calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_SEE_THROUGH = ("bitcast", "tuple")
_PRODUCTS = ("dot", "convolution")


def pass_of(op_name: str) -> str:
    # a parameter's name (``state.step``) is no place in the step either
    if not op_name.startswith("jit("):
        return "none"
    if "transpose(jvp(" in op_name:
        return "remat" if "rematted_computation" in op_name else "backward"
    return "forward" if "jvp(" in op_name else "optimizer"


def scope_of(op_name: str) -> str:
    kept = []
    for part in op_name.split("/")[:-1]:   # the last is the primitive's name
        while (m := _TRANSFORM.match(part)) and m.group(1) != "jit":
            part = m.group(2)
        if part and part not in _WRAPPERS and not _TRANSFORM.match(part) \
                and not _BRANCH.match(part):
            kept.append(part)
    return "/".join(kept)


class _Computation:
    __slots__ = ("insts", "opcode", "operands", "root", "product", "flow",
                 "hint")

    def __init__(self):
        self.insts: list[tuple[str, str, str, str | None]] = []
        self.opcode: dict[str, str] = {}
        self.operands: dict[str, list[str]] = {}
        self.root: str | None = None
        self.product = False
        self.flow: list[str] = []   # computations its control flow runs
        self.hint = ""              # the last op_name inside: nearest its root

    def root_opcode(self) -> str:
        name = self.root
        while self.opcode.get(name) in _SEE_THROUGH and self.operands[name] \
                and self.operands[name][0] in self.opcode:
            name = self.operands[name][0]
        return self.opcode.get(name, "")

    def op_names(self, comps: dict) -> dict[str, str]:
        """Every instruction's op_name: its own where it has one.  What the
        compiler made and left unnamed borrows one: a fusion from the
        instructions it holds (the one nearest its root); then, last to
        first, anything from its first user (a copy between memories and the
        wait for it from the operation they feed, a zero fill from the loop
        it starts, a kernel of the compiler's from what takes its result);
        then, first to last, from its first named operand (a result nothing
        in this computation uses)."""
        own = {}
        for name, _, op_name, callee in self.insts:
            if not op_name.startswith("jit("):
                op_name = comps[callee].hint if callee in comps else ""
            own[name] = op_name
        named = dict(own)
        for name, *_ in reversed(self.insts):
            if named[name]:
                for o in self.operands[name]:
                    if own.get(o) == "":
                        named[o] = named[name]
        for name, *_ in self.insts:
            if not named[name]:
                named[name] = next(
                    (named[o] for o in self.operands[name] if named.get(o)),
                    "")
        return named


def scope_map(hlo_text: str) -> tuple[list[list], dict[str, int]]:
    """``(classes, ops)`` of one optimized HLO module's text: ``ops`` maps
    every instruction of the computations that run as control flow (the
    entry, loop bodies and conditions, branches, calls: what a trace shows
    one event for) to an index into ``classes``, the distinct
    ``[pass, scope, root, product]``.  One pass over the lines, then one
    over the instructions kept."""
    comps: dict[str, _Computation] = {}
    entry = cur = None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m and not line.startswith("HloModule"):
                cur = comps[m.group(2)] = _Computation()
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        name, rest = m.group(2), line[m.end():]
        op = _OPCODE.search(" " + rest)
        opcode = op.group(1) if op else ""
        # operands, then attributes; the metadata is among the last of them
        attrs = rest[op.end() - 1:] if op else rest
        cur.opcode[name] = opcode
        cur.operands[name] = _OPERAND.findall(attrs)
        if m.group(1):
            cur.root = name
        if opcode in _PRODUCTS:
            cur.product = True
        meta = _OP_NAME.search(attrs)
        callee = None
        for kind, target in _CALLEE.findall(attrs):
            if kind == "calls":
                callee = target
            elif kind != "to_apply" or opcode == "call":
                cur.flow.append(target)
        for group in _BRANCHES.findall(attrs):
            cur.flow += [b.strip().lstrip("%") for b in group.split(",")]
        # where the compiler merged instructions it joined their names with
        # ";": the first is the one the instruction started as
        op_name = meta.group(1).split(";")[0] if meta else ""
        if op_name.startswith("jit("):
            cur.hint = op_name
        cur.insts.append((name, opcode, op_name, callee))

    classes: list[list] = []
    index: dict[tuple, int] = {}
    ops: dict[str, int] = {}
    todo, seen = [entry], {entry}
    while todo:
        comp = comps.get(todo.pop())
        if comp is None:
            continue
        named = comp.op_names(comps)
        for name, opcode, _, callee in comp.insts:
            fused = comps.get(callee) if callee else None
            cls = (pass_of(named[name]), scope_of(named[name]),
                   fused.root_opcode() if fused else opcode,
                   fused.product if fused else opcode in _PRODUCTS)
            ops[name] = index.setdefault(cls, len(index))
            if len(index) > len(classes):
                classes.append(list(cls))
        for target in comp.flow:
            if target not in seen:
                seen.add(target)
                todo.append(target)
    return classes, ops


class CacheVerdict:
    """Where one compile came from, by JAX's own account: ``hit`` if the
    persistent cache served an executable to this thread while the context
    was open (``/jax/compilation_cache/cache_hits``), else ``miss``: the
    compiler ran, whether or not its result was worth persisting."""

    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.outcome = "miss"
        self._thread = threading.get_ident()

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT and threading.get_ident() == self._thread:
            self.outcome = "hit"

    def __enter__(self):
        import jax

        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_listener(self._on_event)
        return False


def memory_attrs(compiled) -> dict[str, int]:
    """The compiler's plan for the program's device memory, in bytes; empty
    where the backend gives none."""
    try:
        m = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 — a backend without the analysis
        m = None
    if m is None:
        return {}
    return {"argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes),
            "temp_bytes": int(m.temp_size_in_bytes),
            "code_bytes": int(m.generated_code_size_in_bytes)}


def record_program(tracer, compiled, *, label: str, outcome: str,
                   lower_start: float, compile_start: float,
                   compile_end: float, trace_id=None,
                   parent_id=None) -> None:
    """One ``step_program`` span about one compiled program, from before its
    lowering to after its map is built, and the three children that share
    its clock readings (``program_lower``, ``program_compile``,
    ``program_scopes``), so that their durations sum to its own."""
    if not tracer.enabled:
        return
    text = compiled.as_text()
    classes, ops = scope_map(text)
    module = re.match(r"HloModule ([\w.\-]+)", text)
    attrs = dict(label=label, module=module.group(1) if module else "",
                 outcome=outcome,
                 **memory_attrs(compiled), instructions=len(ops),
                 classes=classes, ops=ops)
    end = time.monotonic()
    sid = tracer.next_span_id()
    tracer.record("step_program", start=lower_start, end=end,
                  trace_id=trace_id, span_id=sid, parent_id=parent_id,
                  **attrs)
    kid = dict(trace_id=trace_id, parent_id=sid, label=label)
    tracer.record("program_lower", start=lower_start, end=compile_start, **kid)
    tracer.record("program_compile", start=compile_start, end=compile_end,
                  **kid)
    tracer.record("program_scopes", start=compile_end, end=end, **kid)
