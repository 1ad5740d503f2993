"""Checkpoint manifests: the layout contract a checkpoint was written under.

A manifest is a JSON sidecar (``<path>.manifest.json``) recording everything
a *different* process — possibly on a *different* topology — needs to know
to restore the state correctly:

- the strategy id and membership epoch the checkpoint was written under,
- the mesh factorization (``R``, ``replica_dcn x replica_ici`` axes) and
  sync hierarchy the arrays are laid out for,
- the per-variable geometry: storage shape (padded partition axes), update-
  space shape (the flat padded 1/R shard of the sharded weight update),
  placement and padding plan.

Two layouts exist:

``"canonical"``
    The classic :meth:`Saver.save` path — everything gathered/unpadded to
    single-device shapes.  R-independent by construction; the manifest is
    informational (provenance + epoch).

``"update_space"``
    The preemption-fast :meth:`Saver.save_sharded` path — params in storage
    layout, optimizer state in the update space (PR 6's permanently-sharded
    1/R flat shards included), **no gather on save**.  Restoring this layout
    requires either the identical geometry (bitwise resume) or the
    resharding path (:mod:`autodist_tpu.checkpoint.reshard`) that re-lays
    the arrays out for an R'-way mesh.

The schema is versioned; consumers must reject a major version they do not
understand (``load_manifest`` does).
"""
import json
import os
import time

import numpy as np

from autodist_tpu.kernel import partitioner as part
from autodist_tpu.utils import logging

SCHEMA_VERSION = 1
MANIFEST_SUFFIX = ".manifest.json"

LAYOUT_CANONICAL = "canonical"
LAYOUT_UPDATE_SPACE = "update_space"


def manifest_path(ckpt_path):
    return str(ckpt_path) + MANIFEST_SUFFIX


def var_geometry(transformer):
    """Per-variable layout records for a transformer's plans: the padding
    plan of the sharded update (flat 1/R shards), partitioned-storage
    padded dims, and divergent-copy leading axes — everything the reshard
    path needs to map a saved leaf back to its canonical shape."""
    out = {}
    for name in transformer.names:
        plan = transformer.plans[name]
        r = transformer._R_for(plan)
        out[name] = {
            "shape": [int(s) for s in plan.shape],
            "dtype": str(np.dtype(plan.dtype)),
            "placement": plan.placement.value,
            "sync": plan.sync.value,
            "partition_axis": int(plan.partition_axis),
            "storage_shape": [int(s) for s in
                              part.storage_shape(plan,
                                                 transformer.num_replicas)],
            "update_shape": [int(s) for s in
                             part.update_space_shape(plan, r)],
            "flat_update": bool(part.flat_shard_update(plan)),
            "sharded_update": bool(plan.sharded_update),
        }
    return out


def build_manifest(transformer, *, step, layout, epoch=0, strategy_id=None):
    """Assemble the manifest dict for a checkpoint about to be written."""
    if layout not in (LAYOUT_CANONICAL, LAYOUT_UPDATE_SPACE):
        raise ValueError(
            f"layout must be {LAYOUT_CANONICAL!r} or "
            f"{LAYOUT_UPDATE_SPACE!r}, got {layout!r}")
    mesh = transformer.mesh
    return {
        "schema": SCHEMA_VERSION,
        "layout": layout,
        "strategy_id": strategy_id
        or getattr(transformer.strategy, "id", ""),
        "step": int(step),
        "epoch": int(epoch),
        "num_replicas": int(transformer.num_replicas),
        "mesh": {
            "axis_names": list(mesh.axis_names),
            "axis_sizes": [int(mesh.shape[a]) for a in mesh.axis_names],
        },
        "data_axes": list(transformer.data_axes),
        "hierarchy": transformer.sync_hierarchy,
        "sharded_update": bool(transformer.sync_sharded_update),
        "sync_schedule": transformer.sync_schedule,
        "accum_steps": int(transformer.accum_steps),
        "vars": var_geometry(transformer),
        "wall_time": time.time(),
    }


def write_manifest(ckpt_path, manifest):
    """Write the sidecar next to the checkpoint (chief process only on
    multi-host — every host would write identical bytes, but racing
    writers on a shared filesystem buy nothing)."""
    import jax

    if jax.process_index() != 0:
        return None
    path = manifest_path(ckpt_path)
    if "://" in path:
        from etils import epath

        epath.Path(path).write_text(json.dumps(manifest, indent=1))
    else:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1)
        os.replace(tmp, path)  # atomic: a preemption mid-write cannot
        #                        leave a truncated manifest behind
    logging.debug("Wrote checkpoint manifest %s (layout=%s step=%d "
                  "epoch=%d R=%d)", path, manifest["layout"],
                  manifest["step"], manifest["epoch"],
                  manifest["num_replicas"])
    return path


def load_manifest(ckpt_path, required=False):
    """Load a checkpoint's manifest; ``None`` when absent (legacy
    checkpoints predate manifests) unless ``required``."""
    path = manifest_path(ckpt_path)
    try:
        if "://" in path:
            from etils import epath

            text = epath.Path(path).read_text()
        else:
            with open(path) as f:
                text = f.read()
    except (FileNotFoundError, OSError):
        if required:
            raise FileNotFoundError(
                f"No checkpoint manifest at {path}; only manifest "
                f"checkpoints (Saver.save / Saver.save_sharded from this "
                f"version on) can be resharded") from None
        return None
    m = json.loads(text)
    if int(m.get("schema", 0)) > SCHEMA_VERSION:
        raise ValueError(
            f"Checkpoint manifest {path} has schema {m.get('schema')}; "
            f"this build understands <= {SCHEMA_VERSION}")
    return m


def geometry_matches(transformer, manifest):
    """Whether a manifest's array layout is bit-identical to what this
    transformer's session holds — the gate between a direct (bitwise)
    restore of an update-space checkpoint and the resharding path.

    Returns ``(ok, reasons)``; ``reasons`` names every mismatch so the
    refusal error (and the reshard log line) can say exactly why.
    """
    reasons = []
    if int(manifest["num_replicas"]) != transformer.num_replicas:
        reasons.append(
            f"num_replicas {manifest['num_replicas']} != "
            f"{transformer.num_replicas}")
    if manifest.get("hierarchy") != transformer.sync_hierarchy:
        # the EF-residual rows of a TWO_LEVEL bucket live in ici-major
        # regions; a hierarchy change lays them out anew even at equal R
        reasons.append(
            f"hierarchy {manifest.get('hierarchy')!r} != "
            f"{transformer.sync_hierarchy!r}")
    here = var_geometry(transformer)
    saved = manifest.get("vars", {})
    if set(saved) != set(here):
        missing = sorted(set(saved) ^ set(here))
        reasons.append(f"variable set differs: {missing[:5]}")
    else:
        for name, e in saved.items():
            h = here[name]
            if e["placement"] != h["placement"]:
                reasons.append(f"{name}: placement {e['placement']} != "
                               f"{h['placement']}")
                continue
            for key in ("storage_shape", "update_shape"):
                if list(e[key]) != list(h[key]):
                    reasons.append(
                        f"{name}: {key} {e[key]} != {h[key]}")
                    break
    return (not reasons), reasons
