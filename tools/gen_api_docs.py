"""Generate the API reference (docs/api/*.md) from live modules.

≙ the reference's sphinx-built docs/python_docs API reference, collapsed
to a dependency-free generator: one markdown file per public namespace
with signatures and docstring summaries, written from the code itself so
the reference can never drift silently.

    python tools/gen_api_docs.py [--out docs/api]
"""
# host-side tool: never touch an accelerator — force the CPU platform
# via the shared helper
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _force_cpu  # noqa: F401  (import has the side effect)

import argparse
import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODULES = [
    ("ndarray", "incubator_mxnet_tpu.ndarray", "NDArray core"),
    ("ndarray.sparse", "incubator_mxnet_tpu.ndarray.sparse",
     "Sparse storage shim (CSR/RSP + device CSR dot)"),
    ("np", "incubator_mxnet_tpu.numpy", "mx.np — NumPy-compatible ops"),
    ("npx", "incubator_mxnet_tpu.numpy_extension",
     "mx.npx — NN / extension ops"),
    ("autograd", "incubator_mxnet_tpu.autograd", "Autograd"),
    ("gluon.nn", "incubator_mxnet_tpu.gluon.nn", "Layers"),
    ("gluon.rnn", "incubator_mxnet_tpu.gluon.rnn", "Recurrent layers"),
    ("gluon.loss", "incubator_mxnet_tpu.gluon.loss", "Losses"),
    ("gluon.metric", "incubator_mxnet_tpu.gluon.metric", "Metrics"),
    ("gluon.data", "incubator_mxnet_tpu.gluon.data", "Data pipeline"),
    ("gluon.probability", "incubator_mxnet_tpu.gluon.probability",
     "Probability distributions + transformations"),
    ("gluon.subgraph", "incubator_mxnet_tpu.gluon.subgraph",
     "Subgraph backend plug-in point"),
    ("model_zoo.vision", "incubator_mxnet_tpu.gluon.model_zoo.vision",
     "Vision model zoo"),
    ("model_zoo.detection",
     "incubator_mxnet_tpu.gluon.model_zoo.detection", "Detection zoo"),
    ("optimizer", "incubator_mxnet_tpu.optimizer", "Optimizers"),
    ("lr_scheduler", "incubator_mxnet_tpu.lr_scheduler", "LR schedules"),
    ("initializer", "incubator_mxnet_tpu.initializer", "Initializers"),
    ("kvstore", "incubator_mxnet_tpu.kvstore", "KVStore"),
    ("parallel", "incubator_mxnet_tpu.parallel",
     "Mesh / collectives / parallelism"),
    ("symbol", "incubator_mxnet_tpu.symbol", "Legacy symbol graph API"),
    ("onnx", "incubator_mxnet_tpu.onnx", "ONNX export"),
    ("amp", "incubator_mxnet_tpu.amp", "Automatic mixed precision"),
    ("contrib.quantization", "incubator_mxnet_tpu.contrib.quantization",
     "INT8 quantization"),
    ("io", "incubator_mxnet_tpu.io", "Legacy data iterators"),
    ("image", "incubator_mxnet_tpu.image", "Image ops"),
    ("recordio", "incubator_mxnet_tpu.recordio", "RecordIO"),
    ("profiler", "incubator_mxnet_tpu.profiler", "Profiler"),
    ("checkpoint", "incubator_mxnet_tpu.checkpoint",
     "Checkpoint / elastic restart"),
    ("library", "incubator_mxnet_tpu.library", "Extension libraries"),
    ("operator", "incubator_mxnet_tpu.operator", "Custom operators"),
    ("engine", "incubator_mxnet_tpu.engine", "Engine facade"),
    ("device", "incubator_mxnet_tpu.device", "Devices / contexts"),
    ("random", "incubator_mxnet_tpu.random", "Random"),
    ("metric", "incubator_mxnet_tpu.metric", "mx.metric alias"),
    ("runtime", "incubator_mxnet_tpu.runtime", "Runtime features"),
]


def _summary(obj):
    doc = inspect.getdoc(obj) or ""
    first = doc.strip().split("\n\n")[0].replace("\n", " ")
    return first[:240]


def _sig(obj):
    try:
        return str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"


def _public_members(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in dir(mod) if not n.startswith("_")]
    out = []
    for n in sorted(set(names)):
        try:
            obj = getattr(mod, n)
        except Exception:
            continue
        if inspect.ismodule(obj):
            continue
        out.append((n, obj))
    return out


def render_module(alias, modname, title):
    import importlib
    mod = importlib.import_module(modname)
    lines = [f"# {title}", "",
             f"`{modname}` (as `mx.{alias}`)", ""]
    head = _summary(mod)
    if head:
        lines += [head, ""]
    classes, funcs, consts = [], [], []
    for n, obj in _public_members(mod):
        if inspect.isclass(obj):
            classes.append((n, obj))
        elif callable(obj):
            funcs.append((n, obj))
        else:
            consts.append((n, obj))
    if classes:
        lines.append("## Classes\n")
        for n, obj in classes:
            lines.append(f"### `{n}{_sig(obj)}`\n")
            s = _summary(obj)
            if s:
                lines.append(s + "\n")
            methods = [(mn, m) for mn, m in inspect.getmembers(obj)
                       if not mn.startswith("_")
                       and callable(m)
                       and mn in obj.__dict__]
            for mn, m in methods:
                ms = _summary(m)
                lines.append(f"- `{mn}{_sig(m)}`"
                             + (f" — {ms}" if ms else ""))
            lines.append("")
    if funcs:
        lines.append("## Functions\n")
        for n, obj in funcs:
            s = _summary(obj)
            lines.append(f"- `{n}{_sig(obj)}`" + (f" — {s}" if s else ""))
        lines.append("")
    return "\n".join(lines) + "\n"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "api"))
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    index = ["# API reference", "",
             "Generated by `python tools/gen_api_docs.py` from the live "
             "modules — regenerate after API changes.", ""]
    n_entries = 0
    for alias, modname, title in MODULES:
        try:
            body = render_module(alias, modname, title)
        except Exception as e:
            print(f"SKIP {modname}: {e}")
            continue
        fname = alias.replace(".", "_") + ".md"
        with open(os.path.join(args.out, fname), "w") as f:
            f.write(body)
        n_members = body.count("\n- `") + body.count("\n### `")
        n_entries += n_members
        index.append(f"- [{title}]({fname}) — `mx.{alias}` "
                     f"({n_members} entries)")
    with open(os.path.join(args.out, "README.md"), "w") as f:
        f.write("\n".join(index) + "\n")
    print(f"wrote {len(MODULES)} pages, ~{n_entries} documented entries "
          f"-> {args.out}")


if __name__ == "__main__":
    main()
