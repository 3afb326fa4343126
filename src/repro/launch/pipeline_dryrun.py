import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run of the PAPER'S OWN workload: the IR pipeline stages
# themselves (multi-model postings scoring + top-k) lowered onto the
# production mesh — queries sharded over 'data' (+ 'pod'), the inverted
# file's postings sharded over 'model'.  This is the §6 "automatic
# parallelisation" future-work of the paper, compiled for 512 chips.
#
#   PYTHONPATH=src python -m repro.launch.pipeline_dryrun [--multi-pod]

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import hlo_cost
from repro.index import scoring
from repro.launch import mesh as mesh_lib

#: the chip the production meshes are priced on
_V5E = hlo_cost.device_peaks("TPU v5 lite")

# ClueWeb09-scale descriptors (never materialised: ShapeDtypeStructs only)
N_DOCS = 50_220_423
MAXQ = 32
MAX_POSTINGS = 4_194_304      # longest non-stop posting list (padded)
N_QUERIES = 512
K = 1000
MODELS = ("BM25", "QL", "TF_IDF")
STATS = {"n_docs": float(N_DOCS), "avg_doclen": 800.0, "total_terms": 4.0e10}


def make_fat_pipeline_step(mesh, dp):
    def fat_pipeline_step(doc_ids, tfs, mask, dl, df, cf, weights):
        """One fused fat-retrieval step for a batch of queries.

        doc_ids/tfs/mask: [NQ, MAXQ, P] gathered postings (P sharded over
        'model').  The dense accumulator is doc-sharded over 'model' too —
        scores scatter locally per index shard, and only the per-query
        top-K (exact, via sharded max-reduction) crosses chips.  This is
        the compiled form of ``Retrieve(BM25) >> (Extract ** Extract)``
        after the fat rewrite, distributed per paper-§6 future work.
        """
        all_s = scoring.score_all(list(MODELS), tfs, dl,
                                  df[..., None], cf[..., None], STATS)
        all_s = all_s * (weights[..., None] * mask)[..., None]
        NQ = doc_ids.shape[0]
        flat_docs = doc_ids.reshape(NQ, -1)
        flat_s = all_s.reshape(NQ, -1, len(MODELS))
        dense = jnp.zeros((NQ, N_DOCS, len(MODELS)), jnp.float32)
        dense = jax.lax.with_sharding_constraint(
            dense, NamedSharding(mesh, P(dp, "model", None)))
        dense = jax.vmap(lambda d, s, i: d.at[i].add(s))(dense, flat_s,
                                                         flat_docs)
        top_s, top_d = jax.lax.top_k(dense[..., 0], K)
        feats = jnp.take_along_axis(dense[..., 1:], top_d[..., None], axis=1)
        return top_d.astype(jnp.int32), top_s, feats
    return fat_pipeline_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    mesh = mesh_lib.make_production_mesh(multi_pod=args.multi_pod)
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    SDS = jax.ShapeDtypeStruct
    shp3 = (N_QUERIES, MAXQ, MAX_POSTINGS)
    shp2 = (N_QUERIES, MAXQ)
    argspec = [
        (SDS(shp3, jnp.int32), P(dp, None, "model")),   # doc_ids
        (SDS(shp3, jnp.int32), P(dp, None, "model")),   # tfs
        (SDS(shp3, jnp.bool_), P(dp, None, "model")),   # mask
        (SDS(shp3, jnp.int32), P(dp, None, "model")),   # dl (per posting)
        (SDS(shp2, jnp.int32), P(dp, None)),            # df
        (SDS(shp2, jnp.int32), P(dp, None)),            # cf
        (SDS(shp2, jnp.float32), P(dp, None)),          # weights
    ]
    in_sh = tuple(NamedSharding(mesh, s) for _, s in argspec)
    out_sh = (NamedSharding(mesh, P(dp, None)),) * 2 + \
        (NamedSharding(mesh, P(dp, None, None)),)

    with mesh:
        lowered = jax.jit(make_fat_pipeline_step(mesh, dp),
                          in_shardings=in_sh,
                          out_shardings=out_sh).lower(
            *[a for a, _ in argspec])
        compiled = lowered.compile()
    walk = hlo_cost.analyze(compiled.as_text())
    mem = compiled.memory_analysis()
    rec = {
        "workload": "fat_pipeline_step (ClueWeb09-scale descriptors)",
        "mesh": "2x16x16" if args.multi_pod else "16x16",
        "flops_per_chip": walk["flops_per_chip"],
        "bytes_per_chip": walk["bytes_per_chip"],
        "collective_bytes_per_chip": walk["collective_bytes_per_chip"],
        "collectives": walk["collectives"],
        "temp_bytes": int(mem.temp_size_in_bytes),
        "t_compute": walk["flops_per_chip"] / _V5E.flops_per_s,
        "t_memory": walk["bytes_per_chip"] / _V5E.bytes_per_s,
        "t_collective": walk["collective_bytes_per_chip"] / _V5E.ici_bytes_per_s,
    }
    tag = "ir_pipeline__" + rec["mesh"]
    Path(args.out).mkdir(parents=True, exist_ok=True)
    (Path(args.out) / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
