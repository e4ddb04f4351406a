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
