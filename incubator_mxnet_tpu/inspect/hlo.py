"""Optimized-HLO text parser for `mx.inspect` (fusion-level attribution).

The compiled module's post-optimization HLO (`jax.stages.Compiled.as_text()`)
is the only backend-portable view of what the chip will actually run: XLA's
fusion passes have already grouped the program into the units that map 1:1
onto kernel launches, so *fusion-level* attribution is the XLA-era analogue
of the reference profiler's per-engine-op attribution (PAPER.md layers 4-6:
`USE_FUSION`, AMP passes decide these boundaries). This parser extracts just
enough structure for the roofline model in `roofline.py`:

  * computations (ENTRY + %fused_computation.* + call wrappers + scan
    bodies), each a list of instructions;
  * per instruction: name, opcode, result shape(s) with dtype, operand
    names + shapes, and the attributes that carry cost information
    (`calls=` for fusions, `to_apply=` for reduce/call, contracting/batch
    dims for dot, `dim_labels` + kernel shape for convolution,
    `metadata.op_name` for attribution back to model code).

No jax import: parsing is plain text so the report/CLI layers stay usable
on artifacts (`--hlo-file dump.txt`) without an accelerator attached.
"""
from __future__ import annotations

import re

__all__ = ["HloInstruction", "HloComputation", "HloModule", "parse_module",
           "parse_shape", "shape_bytes", "DTYPE_BYTES", "scope_of",
           "scope_table"]

# element width in bytes per HLO primitive type (pred is byte-addressed)
DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

# `f32[128,512]{1,0}` / `bf16[]` / `pred[4]{0:T(256)}` (layout tail ignored)
_SHAPE_RE = re.compile(
    r"([a-z][a-z0-9]*)\[([0-9,\s]*)\](?:\{[^}]*\})?")
# one instruction: `[ROOT ]%name = <shape> opcode(<operands>)<attrs>`
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?.*?\)?)\s+"
    r"([\w\-]+)\((.*)$")
_COMP_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{")
_COMMENT_RE = re.compile(r"/\*.*?\*/")
_METADATA_OP_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_DIM_LABELS_RE = re.compile(r"dim_labels=([\w?]+_[\w?]+->[\w?]+)")
_DIMS_RE = re.compile(r"(\w+_dims)=\{([0-9,\s]*)\}")
_FEATURE_GROUPS_RE = re.compile(r"feature_group_count=(\d+)")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
# sigil-less dumps (newer XLA ToString forms drop the '%'): the operand
# name is the trailing identifier after the optional shape text
_BARE_OPERAND_RE = re.compile(r"([A-Za-z_][\w.\-]*)\s*$")


def parse_shape(text):
    """`f32[4,8,8,16]{3,2,1,0}` -> ("f32", (4, 8, 8, 16)). Tuple shapes
    return a list of leaves. Returns None for unparseable text."""
    text = text.strip()
    if text.startswith("("):
        leaves = []
        for m in _SHAPE_RE.finditer(text):
            leaves.append(_leaf(m))
        return leaves or None
    m = _SHAPE_RE.match(text)
    return _leaf(m) if m else None


def _leaf(m):
    dims = tuple(int(d) for d in m.group(2).replace(" ", "").split(",")
                 if d != "")
    return (m.group(1), dims)


def _leaf_bytes(leaf):
    dtype, dims = leaf
    n = 1
    for d in dims:
        n *= d
    return n * DTYPE_BYTES.get(dtype, 4)


def shape_bytes(shape):
    """Total buffer bytes of a parsed shape (tuple shapes sum leaves)."""
    if shape is None:
        return 0
    if isinstance(shape, list):
        return sum(_leaf_bytes(leaf) for leaf in shape)
    return _leaf_bytes(shape)


def num_elements(shape):
    """Element count of a parsed shape (tuples sum leaves; scalars = 1)."""
    if shape is None:
        return 0
    if isinstance(shape, list):
        return sum(num_elements(leaf) for leaf in shape)
    n = 1
    for d in shape[1]:
        n *= d
    return n


class HloInstruction:
    """One parsed HLO instruction (a line of a computation body)."""

    __slots__ = ("name", "opcode", "shape", "operands", "operand_shapes",
                 "called", "op_name", "attrs_text", "is_root")

    def __init__(self, name, opcode, shape, operands, operand_shapes,
                 called, op_name, attrs_text, is_root):
        self.name = name
        self.opcode = opcode
        self.shape = shape                  # parsed result shape
        self.operands = operands            # operand instruction names
        self.operand_shapes = operand_shapes
        self.called = called                # computations this instr calls
        self.op_name = op_name              # metadata op_name (jax source)
        self.attrs_text = attrs_text        # raw attr tail for dims parsing
        self.is_root = is_root

    @property
    def out_bytes(self):
        return shape_bytes(self.shape)

    @property
    def out_elements(self):
        return num_elements(self.shape)

    def dims_attr(self, key):
        """`lhs_contracting_dims` -> (1,) parsed from the attr tail."""
        for m in _DIMS_RE.finditer(self.attrs_text):
            if m.group(1) == key:
                return tuple(int(d) for d in
                             m.group(2).replace(" ", "").split(",")
                             if d != "")
        return ()

    @property
    def dim_labels(self):
        m = _DIM_LABELS_RE.search(self.attrs_text)
        return m.group(1) if m else None

    @property
    def feature_group_count(self):
        m = _FEATURE_GROUPS_RE.search(self.attrs_text)
        return int(m.group(1)) if m else 1

    def __repr__(self):
        return (f"HloInstruction({self.name}: {self.opcode} -> "
                f"{self.shape})")


class HloComputation:
    __slots__ = ("name", "instructions", "is_entry")

    def __init__(self, name, is_entry=False):
        self.name = name
        self.is_entry = is_entry
        self.instructions = []

    @property
    def root(self):
        for ins in self.instructions:
            if ins.is_root:
                return ins
        return self.instructions[-1] if self.instructions else None

    def __repr__(self):
        return (f"HloComputation({self.name}, "
                f"{len(self.instructions)} instrs)")


class HloModule:
    __slots__ = ("name", "computations", "entry_name")

    def __init__(self, name):
        self.name = name
        self.computations = {}
        self.entry_name = None

    @property
    def entry(self):
        if self.entry_name:
            return self.computations.get(self.entry_name)
        return None

    def computation(self, name):
        return self.computations.get(name)

    def __repr__(self):
        return (f"HloModule({self.name}, "
                f"{len(self.computations)} computations)")


def _split_operands(body):
    """Split the operand list at the instruction's top-level closing paren,
    returning (operand_text, attr_tail). Handles nested parens/braces in
    shapes and constants."""
    depth = 1
    for i, ch in enumerate(body):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return body[:i], body[i + 1:]
    return body, ""


def parse_module(text):
    """Parse optimized HLO text (`Compiled.as_text()`) into an HloModule."""
    header = text.splitlines()[0] if text else ""
    mname = "module"
    hm = re.match(r"HloModule\s+([\w.\-]+)", header)
    if hm:
        mname = hm.group(1)
    module = HloModule(mname)
    current = None
    shapes = {}             # instruction name -> result shape, per computation
    for raw in text.splitlines():
        line = raw.rstrip()
        stripped = line.strip()
        if not stripped or stripped.startswith("//"):
            continue
        if stripped.startswith("HloModule"):
            continue
        if stripped == "}":
            current = None
            continue
        cm = _COMP_RE.match(stripped)
        # (a wide tuple parameter prints `/*index=5*/` marks: not an `=`
        # of an instruction)
        if cm and stripped.endswith("{") and "=" not in _COMMENT_RE.sub(
                "", stripped.split("->")[0]):
            comp = HloComputation(cm.group(2), is_entry=bool(cm.group(1)))
            module.computations[comp.name] = comp
            if comp.is_entry:
                module.entry_name = comp.name
            current = comp
            shapes = {}
            continue
        if current is None:
            continue
        im = _INSTR_RE.match(stripped)
        if not im:
            continue
        name, shape_text, opcode, body = im.groups()
        operand_text, attr_tail = _split_operands(body)
        shape = parse_shape(shape_text)
        operands, opshapes = [], []
        # operand entries look like `f32[4,8]{1,0} %name` or, as XLA
        # prints them today, `%name` alone — then the shape is the result
        # shape of the instruction of that name, defined earlier in the
        # same computation. Constants may inline literals — those carry no
        # %name and are skipped (their bytes are trace constants, not HBM
        # traffic)
        for part in _split_top_level(operand_text):
            nm = _OPERAND_NAME_RE.search(part) or \
                _BARE_OPERAND_RE.search(part)
            if not nm:
                continue
            operands.append(nm.group(1))
            opshapes.append(parse_shape(part) or shapes.get(nm.group(1)))
        shapes[name] = shape
        called = [c for c in _CALLS_RE.findall(attr_tail)]
        opm = _METADATA_OP_RE.search(attr_tail)
        current.instructions.append(HloInstruction(
            name, opcode, shape, operands, opshapes, called,
            opm.group(1) if opm else None, attr_tail,
            stripped.startswith("ROOT")))
    return module


def _split_top_level(text):
    """Split an operand list on top-level commas (shapes contain commas
    inside brackets/braces)."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(text):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append(text[start:i])
            start = i + 1
    tail = text[start:]
    if tail.strip():
        parts.append(tail)
    return parts


# -- instruction -> the program's own scope ---------------------------------
# `op_name` is jax's name stack at the equation: the program's
# `jax.named_scope`s between what jax itself adds. These are jax's:
# control flow and call wrappers (dropped), and transforms, which wrap the
# next element (`transpose(jvp(forward))`: unwrapped, and remembered as
# the instruction's pass)
_JAX_ELEMENTS = frozenset((
    "while", "body", "cond", "closed_call", "core_call", "checkpoint",
    "remat", "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_lin", "shard_map", "pallas_call"))
_JAX_BRANCH_RE = re.compile(r"branch_\d+_fun$")
_JAX_WRAP_RE = re.compile(r"([A-Za-z_]\w*)\((.*)\)$")
_JAX_CALLS = frozenset(("jit", "pjit", "xla_call"))


def scope_of(op_name):
    """`(scope path, pass)` of one `op_name`: the path of the scopes the
    PROGRAM chose (`jax.named_scope`), without `jit(...)`, `while/body`,
    `transpose(jvp(...))` and jax's other wrappers and without the
    primitive's name at the end; pass is `"bwd"` under a `transpose(...)`,
    `"fwd"` under a `jvp(...)` alone, `""` under neither.
    `jit(step)/transpose(jvp(forward))/features/4/0/conv` ->
    `("forward/features/4/0", "bwd")`. No `op_name`, or no scope of the
    program's in it -> `("", "")`."""
    if not op_name:
        return "", ""
    kept, which = [], ""
    # (instructions that XLA merged carry both names, `;` between them)
    for elem in op_name.split(";")[0].split("/")[:-1]:
        m = _JAX_WRAP_RE.match(elem)
        while m:
            if m.group(1) == "transpose":
                which = "bwd"
            elif m.group(1) == "jvp" and not which:
                which = "fwd"
            elem = "" if m.group(1) in _JAX_CALLS else m.group(2)
            m = _JAX_WRAP_RE.match(elem)
        if elem and elem not in _JAX_ELEMENTS \
                and not _JAX_BRANCH_RE.match(elem):
            kept.append(elem)
    return "/".join(kept), (which if kept else "")


def scope_table(compiled):
    """`{instruction name: (scope path, pass)}` for every instruction of
    every computation of a compiled program: fusions (a fusion carries its
    root's `op_name`), custom calls, copies, a `while` and what its body
    holds. `compiled` is a `jax.stages.Compiled`, its `as_text()`, or a
    parsed `HloModule`. An instruction the COMPILER added (a re-layout
    copy with no `op_name`) maps to `("", "")`, as does one under no scope
    of the program's. Instruction names are unique in a module, and are
    what a device trace's `XLA Ops` line prints before ` = `."""
    if hasattr(compiled, "as_text"):
        compiled = compiled.as_text()
    module = parse_module(compiled) if isinstance(compiled, str) \
        else compiled
    return {ins.name: scope_of(ins.op_name)
            for comp in module.computations.values()
            for ins in comp.instructions}
