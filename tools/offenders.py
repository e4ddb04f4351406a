"""offenders — fusion-level roofline attribution of a compiled train step.

The ranked, diffable work-list for the kernel tier (ROADMAP item 2): build
a model, wrap it in the flagship `FusedTrainStep` (fwd+loss+bwd+update as
ONE XLA program — the one `resnet50_train.feed` times), lower+compile it,
and walk the optimized HLO through `mx.inspect`: per-fusion flops, bytes
moved, arithmetic intensity, compute- vs memory-bound class against the
calibrated ridge point, and estimated time share. "MFU is 0.15" becomes
"these ten fusions are why".

    python tools/offenders.py --model resnet18 --json out.json
    python tools/offenders.py --model resnet18 --markdown report.md
    python tools/offenders.py --quick                 # CI smoke (tiny net)
    python tools/offenders.py --hlo-file dump.txt     # offline HLO dump
    python tools/offenders.py --model resnet18 --mode infer

Peaks come from `roofline.load_calibration()`: a file named by
`MXNET_INSPECT_CALIB` (`tools/bandwidth.py --calib` writes one; docs/PERF.md
has the workflow), else the platform's table. Knobs: MXNET_INSPECT_TOP_K, MXNET_INSPECT_CALIB.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_step(model, batch_size, layout, mode, use_amp=True,
               use_fusion=None):
    """(step_obj, inputs, execute) for one model name. `execute` runs the
    real program once (wall timing, `--wall`). `use_fusion`
    routes the forward through the fused kernel tier (None = the fused
    steps' MXNET_USE_FUSION default); `--no-fusion` turns it off — the
    before/after offender pair is exactly this A/B."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import amp, gluon
    from incubator_mxnet_tpu import optimizer as opt_mod
    from incubator_mxnet_tpu.gluon.contrib import (FusedInferStep,
                                                   FusedTrainStep)
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    if use_amp:
        amp.init("bfloat16")
    if model == "tiny":
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Conv2D(8, 3, padding=1, layout="NHWC"),
                gluon.nn.Activation("relu"),
                gluon.nn.Conv2D(8, 3, padding=1, layout="NHWC"),
                gluon.nn.GlobalAvgPool2D(layout="NHWC"),
                gluon.nn.Dense(10))
        shape = (batch_size, 8, 8, 3)
        n_classes = 10
    else:
        net = getattr(vision, f"{model}_v1")(layout=layout)
        shape = ((batch_size, 3, 224, 224) if layout == "NCHW"
                 else (batch_size, 224, 224, 3))
        n_classes = 1000
    net.initialize()
    net.hybridize()
    x = mx.np.array(np.random.uniform(-1, 1, shape).astype(np.float32))
    net(x)                                   # resolve deferred shapes
    if mode == "infer":
        step = FusedInferStep(net, use_fusion=use_fusion)
        step(x)                              # seed the chain
        return step, (), lambda: step()
    y = mx.np.array(np.random.randint(0, n_classes, (batch_size,)))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    opt = opt_mod.create("sgd", learning_rate=0.05, momentum=0.9,
                         rescale_grad=1.0 / batch_size)
    step = FusedTrainStep(net, lambda n, a, b: loss_fn(n(a), b).sum(), opt,
                          use_fusion=use_fusion)
    return step, (x, y), lambda: step(x, y)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="offenders", description=__doc__)
    ap.add_argument("--model", default="resnet18",
                    help="model_zoo vision name without the _v1 suffix "
                         "(resnet18, resnet50, ...) or 'tiny'")
    ap.add_argument("--mode", choices=("train", "infer"), default="train")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--layout", default="NHWC")
    ap.add_argument("--no-amp", action="store_true",
                    help="inspect the fp32 program instead of bf16 AMP")
    ap.add_argument("--no-fusion", action="store_true",
                    help="inspect the UNFUSED step (kernel tier off) — "
                         "pair with the default for the before/after "
                         "offender artifacts")
    ap.add_argument("--top-k", type=int, default=None,
                    help="offenders listed (default MXNET_INSPECT_TOP_K)")
    ap.add_argument("--json", nargs="?", const="-", default=None,
                    help="write the report JSON (path, or '-'/bare flag "
                         "for stdout)")
    ap.add_argument("--markdown", nargs="?", const="-", default=None,
                    help="write the markdown report (path or stdout)")
    ap.add_argument("--wall", action="store_true",
                    help="also run the program and report its wall-clock "
                         "time per execution (measured_wall_ms)")
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: tiny net, batch 4")
    ap.add_argument("--hlo-file", default=None,
                    help="analyze a saved HLO text dump offline instead "
                         "of building a model")
    args = ap.parse_args(argv)

    from incubator_mxnet_tpu import inspect as mxinspect

    if args.hlo_file:
        with open(args.hlo_file) as f:
            report = mxinspect.inspect_hlo_text(
                f.read(), name=os.path.basename(args.hlo_file),
                top_k=args.top_k)
    else:
        model = "tiny" if args.quick else args.model
        bs = 4 if args.quick else args.batch_size
        step, inputs, execute = build_step(
            model, bs, args.layout, args.mode, use_amp=not args.no_amp,
            use_fusion=False if args.no_fusion else None)
        report = mxinspect.inspect_step(
            step, *inputs,
            name=f"{model}_{args.mode}_bs{bs}"
                 + ("_unfused" if args.no_fusion else ""),
            top_k=args.top_k,
            execute=execute if args.wall else None)

    if args.markdown:
        text = mxinspect.render_markdown(report)
        if args.markdown == "-":
            print(text)
        else:
            with open(args.markdown, "w") as f:
                f.write(text + "\n")
            print(f"wrote {args.markdown}", file=sys.stderr)
    if args.json:
        if args.json == "-":
            print(json.dumps(report, indent=1, sort_keys=True))
        else:
            mxinspect.dump_json(report, args.json)
            print(f"wrote {args.json}", file=sys.stderr)
    if not args.json and not args.markdown:
        print(mxinspect.render_markdown(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
