import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# Sweep driver: every assigned (arch × shape) on one mesh kind.
#   PYTHONPATH=src python -m repro.launch.dryrun_all --mesh pod

import argparse
import json
import time
import traceback

from repro.common.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--archs", default="")
    ap.add_argument("--shapes", default="")
    args = ap.parse_args()
    enable_compile_cache()

    from repro.common.config import INPUT_SHAPES
    from repro.configs import ARCH_IDS
    from repro.launch.dryrun import run_one

    archs = args.archs.split(",") if args.archs else list(ARCH_IDS)
    shapes = args.shapes.split(",") if args.shapes else list(INPUT_SHAPES)
    t0 = time.time()
    failures = []
    for arch in archs:
        for shape in shapes:
            try:
                run_one(arch, shape, args.mesh, args.out)
            except Exception as e:
                failures.append((arch, shape, repr(e)))
                traceback.print_exc(limit=4)
                res = {"arch": arch, "shape": shape, "mesh": args.mesh,
                       "status": "error", "error": repr(e)}
                os.makedirs(args.out, exist_ok=True)
                with open(os.path.join(
                        args.out, f"{arch}_{shape}_{args.mesh}.json"),
                        "w") as f:
                    json.dump(res, f, indent=2)
    print(f"[dryrun_all] {args.mesh}: done in {time.time()-t0:.0f}s; "
          f"{len(failures)} failures")
    for f in failures:
        print("  FAIL", f)


if __name__ == "__main__":
    main()
