"""Dry-run "profiler": rank collective/HBM-heavy ops in a cell's HLO.

    PYTHONPATH=src python -m repro.roofline.profile --arch rwkv6-1.6b \
        --shape train_4k [--probe] [--extra '{"parallelism":"pure_dp"}']

This is the profile the perf loop reads (no real hardware): the lowered
IR's collective ops ranked by bytes, with op provenance (forward/backward,
which dot_general), plus duplicate-op counts as a remat/redundancy signal.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import json
import re
from collections import Counter


def profile_hlo(hlo: str, top: int = 15) -> dict:
    from repro.roofline.analysis import _OP_RE, _SHAPE_RE, _shape_bytes

    rows = []
    for line in hlo.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        prefix = line[:m.end(1) - len(kind)]
        b = sum(_shape_bytes(dt, dims)
                for dt, dims in _SHAPE_RE.findall(prefix))
        mm = re.search(r'op_name="([^"]+)"', line)
        meta = mm.group(1) if mm else ""
        shapes = _SHAPE_RE.findall(prefix)
        rows.append((b, kind, shapes[:2], meta[-80:]))
    rows.sort(key=lambda r: -r[0])
    total = sum(r[0] for r in rows)
    # remat signal: identical op_name stems appearing many times
    stems = Counter(re.sub(r"\d+", "", r[3]) for r in rows)
    return {"total_bytes": total, "count": len(rows), "top": rows[:top],
            "dup_stems": stems.most_common(5)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--probe", action="store_true",
                    help="profile the (1,1) probe instead of the full cell")
    ap.add_argument("--extra", default=None)
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args()

    from repro.launch import cells
    from repro.launch.mesh import make_production_mesh

    extra = json.loads(args.extra) if args.extra else None
    mesh = make_production_mesh()
    if args.probe:
        built = cells.build_probe(args.arch, args.shape, mesh, periods=1,
                                  microbatches=1, extra_config=extra)
    else:
        built = cells.build_cell(args.arch, args.shape, mesh,
                                 extra_config=extra)
    hlo = built.lowered.compile().as_text()
    prof = profile_hlo(hlo, args.top)
    print(f"collective ops: {prof['count']}, total "
          f"{prof['total_bytes'] / 2**30:.3f} GiB/device")
    for b, kind, shapes, meta in prof["top"]:
        print(f"{b / 2**20:9.1f}MiB {kind:18s} {shapes} {meta}")


if __name__ == "__main__":
    main()
