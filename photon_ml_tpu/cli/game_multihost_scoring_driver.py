"""Multi-host SPMD GAME scoring driver: score datasets against models that
NO single host ever holds.

Every host runs the same program under ``jax.distributed``: it loads only
its share of the random-effect model's part files
(ModelProcessingUtils.scala:205-219 layout — the same per-partition model
files the multihost TRAINING driver writes), routes each model record to
its entity's owner device with the stable-hash shuffle, decodes only its
slice of the input rows, routes them to the owners for scoring
(parallel.perhost_ingest.score_routed_rows), and writes its own scores
part file. The fixed-effect model is small and replicated (the broadcast
analogue). This is how a "hundreds of billions of coefficients" model
(reference README.md:73) is SCORED: coefficients stay sharded end to end
— loaded sharded, stored sharded, applied sharded.

Factored/MF models score latent-native: the shared (k, D) matrix is
replicated (it is tiny), each host loads its share of the latent-factor
part files, rows are projected into the k-dim latent space host-side and
routed exactly like a plain random effect in a k-dim feature space.

Scope (v1): AVRO inputs, prebuilt feature maps (--offheap-indexmap-dir).

Run (one process per host):

    python -m photon_ml_tpu.cli.game_multihost_scoring_driver \\
        --multihost-coordinator HOST:PORT --multihost-num-processes N \\
        --multihost-process-id I  <game scoring flags...>
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from photon_ml_tpu.cli.game_multihost_driver import _add_multihost_flags
from photon_ml_tpu.cli.game_params import parse_scoring_params
from photon_ml_tpu.cli.game_scoring_driver import SCORES_DIR
from photon_ml_tpu.cli.game_training_driver import (
    _input_files,
    resolve_date_range_dirs,
)
from photon_ml_tpu.io import avro as avro_io
from photon_ml_tpu.io import model_io, schemas
from photon_ml_tpu.io.avro_data import read_game_data
from photon_ml_tpu.parallel import multihost
from photon_ml_tpu.parallel.perhost_ingest import (
    concat_host_rows,
    csr_to_padded,
    global_row_layout,
    host_file_share,
    HostRows,
    merge_row_vectors,
    per_host_model_slabs,
    score_routed_rows,
)
from photon_ml_tpu.parallel.shuffle import collective_sum
from photon_ml_tpu.utils.io_utils import prepare_output_dir
from photon_ml_tpu.utils.logging import PhotonLogger


def _load_re_model_rows(base: str, part_files: List[str], index_map):
    """Decode THIS host's share of one RE model's part files into sparse
    per-entity coefficient rows (global indices)."""
    ids: List[str] = []
    idx_rows: List[np.ndarray] = []
    val_rows: List[np.ndarray] = []
    for f in part_files:
        for rec in avro_io.read_container(os.path.join(base, f)):
            cols, vals = [], []
            for ntv in rec["means"]:
                j = model_io.ntv_index(ntv, index_map)
                if j >= 0:
                    cols.append(j)
                    vals.append(ntv["value"])
            ids.append(rec["modelId"])
            idx_rows.append(np.asarray(cols, np.int32))
            val_rows.append(np.asarray(vals, np.float32))
    k = max((len(c) for c in idx_rows), default=1)
    k = max(k, 1)
    fi = np.full((len(ids), k), -1, np.int32)
    fv = np.zeros((len(ids), k), np.float32)
    for i, (c, v) in enumerate(zip(idx_rows, val_rows)):
        fi[i, : len(c)] = c
        fv[i, : len(c)] = v
    return ids, fi, fv


def main(argv: Optional[List[str]] = None) -> dict:
    import sys

    mh_args, rest = _add_multihost_flags(
        list(argv) if argv is not None else sys.argv[1:]
    )
    p = parse_scoring_params(rest)
    mh = multihost.initialize(
        coordinator_address=mh_args["coordinator"],
        num_processes=mh_args["num_processes"],
        process_id=mh_args["process_id"],
    )
    ctx = mh.mesh_context()
    if mh.coordinator_only_io():
        prepare_output_dir(p.output_dir, p.delete_output_dir_if_exists)
    mh.barrier("output-dir")
    logger = PhotonLogger(
        os.path.join(p.output_dir, f"photon-ml-tpu-mh-scoring-{mh.process_id}.log")
    )
    from photon_ml_tpu import compat

    compat.start_up(logger.info)
    if not p.offheap_indexmap_dir:
        raise ValueError(
            "multihost scoring needs prebuilt feature maps: pass "
            "--offheap-indexmap-dir (a full-data vocabulary scan per host "
            "defeats per-host ingest)"
        )

    # ---- model layout -----------------------------------------------------
    layout = model_io.list_game_model(p.game_model_input_dir)
    fixed, random = [], []
    for name in layout[model_io.FIXED_EFFECT]:
        base = os.path.join(p.game_model_input_dir, model_io.FIXED_EFFECT, name)
        with open(os.path.join(base, model_io.ID_INFO)) as f:
            fixed.append((name, f.read().strip()))
    for name in layout[model_io.RANDOM_EFFECT]:
        base = os.path.join(p.game_model_input_dir, model_io.RANDOM_EFFECT, name)
        with open(os.path.join(base, model_io.ID_INFO)) as f:
            lines = f.read().splitlines()
        random.append((
            name, lines[0], lines[1] if len(lines) > 1 else "",
            model_io.is_factored_random_effect(p.game_model_input_dir, name),
        ))

    from photon_ml_tpu.io.offheap import load_shard_index_map

    shards = sorted(
        {s for _, s in fixed if s} | {s for _, _, s, _ in random if s}
    )
    shard_maps = {s: load_shard_index_map(p.offheap_indexmap_dir, s) for s in shards}
    grouped_ids = sorted({idn for _, _, idn in (p.evaluators or []) if idn})
    id_types = sorted(
        set(p.random_effect_id_types)
        | {rid for _, rid, _, _ in random if rid}
        | set(grouped_ids)
    )

    # ---- per-host input decode -------------------------------------------
    # _input_files is deterministic (per-dir sorted, dirs in argument
    # order) and identical on every host — NO global re-sort, so uid/row
    # order matches the single-process scoring driver exactly
    all_files = _input_files(
        resolve_date_range_dirs(p.input_dirs, p.date_range, p.date_range_days_ago)
    )
    host_files = host_file_share(all_files, mh.num_processes, mh.process_id)
    gds = []
    for f, ordinal in host_files:
        gd = read_game_data(
            [f], shard_maps, p.feature_shard_sections, id_types,
            shard_intercepts=p.feature_shard_intercepts or None,
            # evaluators need labels; pure inference tolerates nulls (the
            # single-process driver's rule)
            response_required=bool(p.evaluators),
        )
        gds.append((ordinal, gd))
    file_base, n_global = global_row_layout(
        len(all_files), gds, ctx, mh.num_processes
    )
    logger.info(
        f"host {mh.process_id}: scoring {sum(gd.num_rows for _, gd in gds)}"
        f"/{n_global} rows ({len(host_files)}/{len(all_files)} files)"
    )

    def merge(vec_per_gd):
        return merge_row_vectors(
            gds, file_base, n_global, ctx, mh.num_processes, vec_per_gd
        )

    scores = merge(lambda gd: gd.offset.astype(np.float32)).astype(np.float64)

    # ---- fixed effects: replicated model, local margins -------------------
    for name, shard in fixed:
        means, _, _, _ = model_io.load_fixed_effect(
            p.game_model_input_dir, name, shard_maps[shard]
        )
        local = np.zeros(n_global, np.float32)
        for ordinal, gd in gds:
            f = gd.shards[shard]
            fi, fv = csr_to_padded(f, gd.num_rows)
            sel = np.where(fi >= 0, means[np.maximum(fi, 0)], 0.0)
            local[file_base[ordinal] + np.arange(gd.num_rows)] = np.sum(
                sel * fv, axis=1
            )
        scores += collective_sum(local, ctx, mh.num_processes)

    # ---- random effects: per-host model parts -> owner slabs -> routing ---
    for name, re_id, shard, factored in random:
        if factored:
            # latent-native: v_e (k,) per entity + shared (k, D) matrix.
            # Each host loads its share of the latent-factor part files;
            # the tiny matrix is replicated and rows are PROJECTED into the
            # k-dim latent space host-side before routing — after that the
            # scoring math is identical to a plain RE in a k-dim space.
            fbase = os.path.join(
                p.game_model_input_dir, model_io.RANDOM_EFFECT, name,
            )
            # ONLY the tiny matrix is loaded whole; the per-entity latent
            # factors are read per host below (sharded end to end)
            matrix = model_io.load_latent_matrix(p.game_model_input_dir, name)
            matrix_aligned = model_io.aligned_latent_matrix(
                p.game_model_input_dir, name, shard_maps[shard],
                matrix, warn=logger.warn,
            )
            lat_dir = os.path.join(fbase, model_io.LATENT_FACTORS)
            parts = sorted(f for f in os.listdir(lat_dir) if f.endswith(".avro"))
            my_parts = [f for f, _ in host_file_share(
                parts, mh.num_processes, mh.process_id
            )]
            ids, vecs = [], []
            for f in my_parts:
                for rec in avro_io.read_container(os.path.join(lat_dir, f)):
                    ids.append(rec["effectId"])
                    vecs.append(np.asarray(rec["latentFactor"], np.float32))
            k_lat = matrix.shape[0]
            fv_m = (np.stack(vecs) if vecs
                    else np.zeros((0, k_lat), np.float32))
            fi_m = np.tile(np.arange(k_lat, dtype=np.int32), (len(ids), 1))
            logger.info(
                f"factored effect {name!r}: host {mh.process_id} loaded "
                f"{len(ids)} latent factors "
                f"({len(my_parts)}/{len(parts)} part files)"
            )
            sd, w = per_host_model_slabs(
                ids, fi_m, fv_m, k_lat, ctx, mh.num_processes, mh.process_id,
            )
            vparts = []
            for ordinal, gd in gds:
                f = gd.shards[shard]
                fi, fv = csr_to_padded(f, gd.num_rows)
                # xp = x @ M^T via the padded sparse encoding, accumulated
                # one padded column at a time: O(n*k) memory (a (k, n, K)
                # gather would be k*n*K floats — the memory-scaling the
                # driver exists to avoid). csr_to_padded zero-fills padding
                # values, so masked-column contributions are exact 0s.
                xp = np.zeros((gd.num_rows, matrix_aligned.shape[0]), np.float32)
                for j in range(fi.shape[1]):
                    xp += fv[:, j, None] * matrix_aligned[:, np.maximum(fi[:, j], 0)].T
                vocab = gd.id_vocabs[re_id]
                vparts.append(HostRows(
                    entity_raw_ids=[vocab[i] for i in gd.ids[re_id]],
                    row_index=file_base[ordinal]
                    + np.arange(gd.num_rows, dtype=np.int64),
                    labels=np.nan_to_num(gd.response).astype(np.float32),
                    weights=gd.weight.astype(np.float32),
                    offsets=gd.offset.astype(np.float32),
                    feat_idx=np.tile(
                        np.arange(k_lat, dtype=np.int32), (gd.num_rows, 1)
                    ),
                    feat_val=xp.astype(np.float32),
                    global_dim=k_lat,
                ))
            vrows = concat_host_rows(vparts, k_lat)
            scores += score_routed_rows(
                sd, w, vrows, n_global, ctx, mh.num_processes, mh.process_id
            )
            continue
        base = os.path.join(
            p.game_model_input_dir, model_io.RANDOM_EFFECT, name,
            model_io.COEFFICIENTS,
        )
        parts = sorted(f for f in os.listdir(base) if f.endswith(".avro"))
        my_parts = [f for f, _ in host_file_share(
            parts, mh.num_processes, mh.process_id
        )]
        ids, fi_m, fv_m = _load_re_model_rows(base, my_parts, shard_maps[shard])
        logger.info(
            f"random effect {name!r}: host {mh.process_id} loaded "
            f"{len(ids)} of the model's entities "
            f"({len(my_parts)}/{len(parts)} part files)"
        )
        sd, w = per_host_model_slabs(
            ids, fi_m, fv_m, len(shard_maps[shard]), ctx,
            mh.num_processes, mh.process_id,
        )
        row_parts = []
        for ordinal, gd in gds:
            f = gd.shards[shard]
            fi, fv = csr_to_padded(f, gd.num_rows)
            vocab = gd.id_vocabs[re_id]
            row_parts.append(HostRows(
                entity_raw_ids=[vocab[i] for i in gd.ids[re_id]],
                row_index=file_base[ordinal] + np.arange(gd.num_rows, dtype=np.int64),
                labels=np.nan_to_num(gd.response).astype(np.float32),
                weights=gd.weight.astype(np.float32),
                offsets=gd.offset.astype(np.float32),
                feat_idx=fi, feat_val=fv,
                global_dim=f.dim,
            ))
        vrows = concat_host_rows(row_parts, len(shard_maps[shard]))
        scores += score_routed_rows(
            sd, w, vrows, n_global, ctx, mh.num_processes, mh.process_id
        )

    scores = scores.astype(np.float32)

    # ---- save: each host writes its own scores part files -----------------
    out = os.path.join(p.output_dir, SCORES_DIR)
    if mh.coordinator_only_io():
        os.makedirs(out, exist_ok=True)
    mh.barrier("scores-dir")
    for ordinal, gd in gds:
        base_id = int(file_base[ordinal])

        def records():
            for r in range(gd.num_rows):
                label = float(gd.response[r])
                yield {
                    "uid": str(base_id + r),
                    "label": None if np.isnan(label) else label,
                    "modelId": p.game_model_id,
                    "predictionScore": float(scores[base_id + r]),
                    "weight": float(gd.weight[r]),
                    "metadataMap": None,
                }

        avro_io.write_container(
            os.path.join(out, f"part-{ordinal:05d}.avro"),
            records(),
            schemas.SCORING_RESULT,
        )
    mh.barrier("scores-written")

    # ---- optional evaluators (replicated labels/weights) ------------------
    metrics: Dict[str, float] = {}
    if p.evaluators:
        from photon_ml_tpu.evaluation.evaluators import evaluator_for
        from photon_ml_tpu.parallel.perhost_ingest import merge_group_ids

        labels = merge(lambda gd: gd.response.astype(np.float32))
        weights = merge(lambda gd: gd.weight.astype(np.float32))
        group_cols = {
            idn: jnp.asarray(merge_group_ids(
                gds, file_base, n_global, idn, ctx, mh.num_processes
            ))
            for idn in grouped_ids
        }
        for etype, k, id_name in p.evaluators:
            ev = evaluator_for(etype, k or 10)
            kwargs = {"labels": jnp.asarray(labels),
                      "weights": jnp.asarray(weights)}
            if id_name is not None:
                kwargs["group_ids"] = group_cols[id_name]
            key = etype.value if k is None else f"{etype.value}@{k}"
            metrics[key] = float(ev.evaluate(jnp.asarray(scores), **kwargs))
        if mh.coordinator_only_io():
            logger.info(
                "metrics: " + " ".join(f"{k}={v:.6g}" for k, v in metrics.items())
            )
    logger.info(f"wrote scores to {out}")
    logger.close()
    return {
        "num_rows": n_global,
        "metrics": metrics,
        "process_id": mh.process_id,
        "scores_dir": out,
    }


if __name__ == "__main__":
    main()
