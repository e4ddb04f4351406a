"""Where a compiled program keeps its sorts: the sampler's sort of the
whole vocabulary has to sit on one side of an on-device `conditional`
(`serve.sampling.sample_tokens`), so a `vmap` or a refactor that evaluates
both sides fails a test and not a benchmark."""
from incubator_mxnet_tpu.inspect import hlo


def sorts_and_conditionals(compiled):
    """`(sorts, conditionals, unguarded)` of a `jax.stages.Compiled`: how
    many `sort` and `conditional` instructions its optimized HLO holds, and
    the sorts that run whenever the program runs — those reached from ENTRY
    through fusions, calls, reducers and loop bodies without entering a
    conditional's branch computation (`HloInstruction.called` names
    `calls=`, `to_apply=`, `body=` and `condition=`, never a branch)."""
    module = hlo.parse_module(compiled.as_text())
    count = {"sort": 0, "conditional": 0}
    for comp in module.computations.values():
        for ins in comp.instructions:
            if ins.opcode in count:
                count[ins.opcode] += 1
    unguarded, seen, stack = [], set(), [module.entry_name]
    while stack:
        name = stack.pop()
        if name in seen or name not in module.computations:
            continue
        seen.add(name)
        for ins in module.computations[name].instructions:
            if ins.opcode == "sort":
                unguarded.append(ins.name)
            stack.extend(ins.called)
    return count["sort"], count["conditional"], unguarded


# opcodes whose result names another instruction's buffer
_NO_BUFFER = {"parameter", "tuple", "get-tuple-element", "while", "bitcast"}


def buffers_of_at_least(compiled, elements):
    """Names of the instructions of a `jax.stages.Compiled` that make a
    buffer of `elements` elements or more: a result (or one element of a
    tuple result) that large, but for what only names another
    instruction's buffer (parameters, tuples and their elements, the
    `while` that carries them, bitcasts) and a `dynamic-update-slice`,
    alone or as a fusion's root, which writes into its first operand. The
    row copy of the prefix cache (`serve.continuous._copy_slot_rows`) has
    to come out empty at one row's elements: its temporaries are pieces,
    and a gather of rows or a copy of the slab fails a test and not a
    benchmark."""
    module = hlo.parse_module(compiled.as_text())

    def updates_in_place(ins):
        if ins.opcode == "fusion":
            return any(updates_in_place(module.computations[c].root)
                       for c in ins.called if c in module.computations)
        return ins.opcode == "dynamic-update-slice"

    found = []
    for comp in module.computations.values():
        for ins in comp.instructions:
            leaves = ins.shape if isinstance(ins.shape, list) else [ins.shape]
            if (ins.opcode not in _NO_BUFFER and not updates_in_place(ins)
                    and any(hlo.num_elements(leaf) >= elements
                            for leaf in leaves if leaf is not None)):
                found.append(ins.name)
    return found


def slab_slices(lowered, slab_shape):
    """Result shapes of the `slice`s in a `jax.stages.Lowered` that keep
    every row and every layer of a cache slab of `slab_shape` (rows,
    layers, positions, ...) and cut it elsewhere: a bound on the positions
    a read may see, written as a slice of the whole slab, is a copy of
    the slab wherever the read wants its operand whole (the classic
    decoder's chunk program made 48 of them, 1.9 GB each at the
    benchmark cell's shapes). A read of ONE layer's rows (`slab[:S, l]`,
    the jnp fallback's) is not one of them."""
    import re
    lead = "x".join(str(d) for d in slab_shape[:2]) + "x"
    return [shape for shape in re.findall(
        r"stablehlo\.slice [^\n]*-> tensor<((?:\d+x)+)\w+>",
        lowered.as_text()) if shape.startswith(lead)]
