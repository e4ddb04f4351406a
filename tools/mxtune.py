#!/usr/bin/env python
"""mxtune — deployment-profile autotuner CLI.

Sweep the declared knob catalog for a (model, hardware) deployment,
report the winners against the hand-tuned committed baselines, and
persist the profile beside the compile cache so the next replica boots
warm AND tuned.

    # what would run, without running it
    python tools/mxtune.py --phases serve_decode --dry-run

    # sweep two phases with a 16-trial budget, write the profile
    python tools/mxtune.py --model model_spec.json \
        --phases serve_decode,train_fused --budget 16 \
        --write-profile --json tune_report.json

`--model` is a JSON file whose contents identify the deployment (a
DecoderConfig dict, an export manifest, ...); its canonical hash is the
profile's model fingerprint. Without it the profile is keyed to the
empty model meta (tuning host-generic knobs like io/dispatch).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _markdown(res, prof):
    lines = ["# mxtune report", ""]
    if prof is not None:
        lines += [f"profile `{prof.profile_hash}` — model "
                  f"`{prof.model_fp}`, hardware `{prof.hw_fp}`", ""]
    lines += ["| phase | hand score | best score | speedup | trials "
              "| failed |", "|---|---|---|---|---|---|"]
    for p, d in sorted(res["phases"].items()):
        base = (d.get("baseline") or {}).get("score")
        best = (d.get("best") or {}).get("score")
        unit = (d.get("best") or {}).get("unit") or ""
        failed = sum(1 for t in d["trials"] if not t["ok"])
        lines.append(
            f"| {p} | {base} | {best} {unit} | "
            f"{d.get('speedup_vs_hand')} | {len(d['trials'])} | "
            f"{failed} |")
    lines += ["", "## winning knobs", ""]
    for k, v in sorted(res["knobs"].items()):
        lines.append(f"- `{k}` = `{v!r}`")
    lines.append("")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="mxtune", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--model", help="JSON file identifying the model "
                    "(fingerprint source)")
    ap.add_argument("--phases", help="comma-separated tune phases "
                    "(default: every phase the catalog declares)")
    ap.add_argument("--budget", type=int, default=None,
                    help="total trial budget (default MXNET_TUNE_BUDGET "
                    "or 24)")
    ap.add_argument("--scale", default="full",
                    choices=("quick", "full"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", help="write the full sweep result here")
    ap.add_argument("--markdown", help="write a markdown report here")
    ap.add_argument("--write-profile", nargs="?", const="", default=None,
                    metavar="DIR", help="persist the winning profile "
                    "(optionally into DIR; default: the profile dir)")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the deterministic trial schedule and "
                    "exit without measuring")
    args = ap.parse_args(argv)

    from incubator_mxnet_tpu import tune

    model_meta = {}
    if args.model:
        with open(args.model) as f:
            model_meta = json.load(f)
    phases = (args.phases.split(",") if args.phases
              else [p for p in tune.phases() if p in tune.HAND_TUNED])

    if args.dry_run:
        for p in phases:
            sched = tune.plan(p, budget=args.budget)
            print(f"phase {p}: {len(sched)} trials")
            for i, asn in enumerate(sched):
                tag = "hand-tuned baseline" if i == 0 else ""
                print(f"  [{i:3d}] {json.dumps(asn, sort_keys=True)} "
                      f"{tag}")
        return 0

    res = tune.sweep(phases=phases, budget=args.budget, seed=args.seed,
                     scale=args.scale)
    prof = None
    if res["knobs"]:
        prof = tune.build_profile(res, model_meta=model_meta)
    for p, d in sorted(res["phases"].items()):
        print(f"phase {p}: hand={(d['baseline'] or {}).get('score')} "
              f"best={(d['best'] or {}).get('score')} "
              f"speedup={d.get('speedup_vs_hand')} "
              f"({len(d['trials'])} trials, "
              f"{sum(1 for t in d['trials'] if not t['ok'])} failed)")
    if args.json:
        payload = dict(res)
        if prof is not None:
            payload["profile"] = prof.to_dict()
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    if args.markdown:
        with open(args.markdown, "w") as f:
            f.write(_markdown(res, prof))
        print(f"wrote {args.markdown}")
    if args.write_profile is not None:
        if prof is None:
            print("no successful trials — nothing to persist",
                  file=sys.stderr)
            return 1
        path = prof.save(directory=args.write_profile or None)
        print(f"profile {prof.profile_hash} -> {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
