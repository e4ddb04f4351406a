#!/usr/bin/env python
"""Convert a reference-format .params checkpoint into the offline npz zoo.

≙ the role of python/mxnet/gluon/model_zoo/model_store.py's download+cache:
this build is offline, so checkpoints are converted locally once and then
`model_zoo.vision.<model>(pretrained=True, root=...)` loads them.

    python tools/convert_model.py resnet18_v1.params ~/.mxnet/models/resnet18_v1.npz
    python tools/convert_model.py net.params out.npz --rename old=new --rename a=b
    python tools/convert_model.py zoo.params out.npz --auto-map resnet50_v1

--auto-map <model>: derive the rename table automatically by aligning the
checkpoint's parameters with this framework's model of the same
architecture in construction order, validating every pair's shape — real
reference zoo files use flat scoped names (resnetv10_conv0_weight...)
that differ from the structural names here; the architectures enumerate
identically, so order+shape alignment maps them without a curated table.
"""
# host-side tool: never touch an accelerator — force the CPU platform
# via the shared helper
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _force_cpu  # noqa: F401  (import has the side effect)

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("params_file")
    ap.add_argument("npz_file")
    ap.add_argument("--rename", action="append", default=[],
                    help="old=new parameter renames (repeatable)")
    ap.add_argument("--auto-map", default=None, metavar="MODEL",
                    help="derive renames by order+shape alignment against "
                         "a model-zoo architecture (e.g. resnet50_v1)")
    args = ap.parse_args()
    from incubator_mxnet_tpu.gluon.model_zoo.model_store import (
        convert_params_to_npz)
    name_map = dict(r.split("=", 1) for r in args.rename)
    if args.auto_map:
        from incubator_mxnet_tpu.gluon.model_zoo.model_store import (
            auto_name_map)
        auto = auto_name_map(args.params_file, args.auto_map)
        auto.update(name_map)   # explicit --rename entries win
        name_map = auto
        print(f"auto-map: aligned {len(auto)} parameters")
    out = convert_params_to_npz(args.params_file, args.npz_file,
                                name_map or None)
    import numpy as np
    with np.load(out) as f:
        print(f"wrote {out}: {len(f.files)} arrays")


if __name__ == "__main__":
    main()
