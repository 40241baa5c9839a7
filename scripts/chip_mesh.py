"""Where a mesh run puts things: ``ShardedScorer`` at the flagship width over
every device jax reports, read back from ``addressable_shards``.

Builder-side evidence for the multi-chip host (``chip_smoke.py --mesh data=4``
drives the same executor through the service and reports per-device HBM; this
says which shard of which array sits where, which the service has no business
exposing). One process; checks that the largest param leaf and a full-width
token batch each have a shard on every mesh device, and that the mesh's scores
for that batch agree with one device's for the same params.

Prints one JSON line; exit code 1 if a check failed.

Usage: python scripts/chip_mesh.py [--tiny]     # --tiny: CPU-sized model
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def shards(array) -> list:
    return [[str(s.device), list(s.data.shape)]
            for s in array.addressable_shards]


def check_mesh(config: dict, batch: int) -> dict:
    import jax
    import numpy as np

    from detectmateservice_tpu.models.logbert import LogBERTConfig, LogBERTScorer
    from detectmateservice_tpu.parallel.mesh import make_mesh
    from detectmateservice_tpu.parallel.sharded import ShardedScorer

    devices = jax.devices()
    scorer = LogBERTScorer(LogBERTConfig(platform=devices[0].platform,
                                         **config))
    sharded = ShardedScorer(scorer, mesh=make_mesh({"data": len(devices)}),
                            rng=jax.random.PRNGKey(0))
    tokens = np.random.default_rng(0).integers(
        1, config["vocab_size"], (batch, config["seq_len"]), dtype=np.int32)
    placed = sharded.place(tokens)
    leaf = max(jax.tree_util.tree_leaves(sharded.params), key=lambda x: x.size)
    mesh_scores = sharded.score(tokens)
    one = jax.device_put(jax.device_get(sharded.params), devices[0])
    one_scores = np.asarray(jax.jit(scorer._score_impl)(
        one, jax.device_put(tokens, devices[0])))
    want = {str(d) for d in devices}
    report = {
        "platform": devices[0].platform, "device_kind": devices[0].device_kind,
        "devices": sorted(want),
        "largest_param": {"shape": list(leaf.shape), "shards": shards(leaf)},
        "batch": {"shape": list(placed.shape), "shards": shards(placed)},
        "scores_finite": bool(np.isfinite(mesh_scores).all()),
        "mesh_vs_one_device_max_abs_diff": float(
            np.abs(mesh_scores.astype(np.float32)
                   - one_scores.astype(np.float32)).max()),
    }
    report["ok"] = (
        {dev for dev, _ in report["largest_param"]["shards"]} == want
        and {dev for dev, _ in report["batch"]["shards"]} == want
        and all(shape == [batch // len(devices), config["seq_len"]]
                for _, shape in report["batch"]["shards"])
        and report["scores_finite"]
        and report["mesh_vs_one_device_max_abs_diff"] < 0.05)
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    if args.tiny:
        config, batch = dict(vocab_size=2048, dim=32, depth=1, heads=2,
                             seq_len=32), 64
    else:
        config, batch = dict(vocab_size=32768, dim=256, depth=4, heads=4,
                             seq_len=32), 16384
    report = check_mesh(config, batch)
    print(json.dumps(report))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
