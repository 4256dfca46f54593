"""Device mesh construction and named sharding axes.

TPU-native equivalent of the reference's parallelism inventory (SURVEY.md
§2.3). The reference composes FSDP sharding + rollout dp×infer_tp×infer_pp
meshes (``stream_fsdp_workers.py:126-135``) + Ulysses SP; here all of it is
one ``jax.sharding.Mesh`` with five logical axes:

- ``dp``    data parallel (batch dim)
- ``fsdp``  ZeRO-style parameter sharding (combines with dp for the batch)
- ``tp``    tensor/model parallel (MXU-dim sharding, rides ICI)
- ``sp``    sequence/context parallel (Ulysses all-to-all or ring attention)
- ``ep``    expert parallel (MoE expert dim; each rank computes its own
            experts' rows and the results are summed over ep)
- ``pp``    pipeline parallel (layer-stack stages; GPipe microbatch
            schedule via shard_map + ppermute, parallel/pipeline.py)

Training batches shard over (dp, fsdp); params shard over (fsdp, tp) with
MoE expert weights additionally over ep and the layer stack over pp;
sequence dim over sp. XLA inserts the collectives (GSPMD), so FSDP
all-gather/reduce-scatter and the TP broadcast of the reference's NCCL
world disappear into the compiled program.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DP, FSDP, TP, SP, EP, PP = "dp", "fsdp", "tp", "sp", "ep", "pp"
AXES = (DP, FSDP, TP, SP, EP, PP)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    dp: int = 1
    fsdp: int = -1  # -1: absorb remaining devices
    tp: int = 1
    sp: int = 1
    # Pipeline parallelism: a REAL axis (beyond the reference, which only
    # stubs infer_pp, workers/config/rollout.py:132-134,198-202) — the
    # layer stack reshapes to [pp, L/pp, ...] sharded over it and runs the
    # GPipe microbatch schedule (parallel/pipeline.py: shard_map +
    # ppermute; autodiff through the permutes gives the backward schedule).
    pp: int = 1
    # Expert parallelism: a REAL axis (beyond the reference, which stubs
    # expert knobs at workers/config/rollout.py:193-196) — MoE expert
    # weights shard over it (models/decoder.py MoE param specs) and each
    # rank computes its own experts' rows (blocks._expert_mix_sharded).
    ep: int = 1

    def resolve(self, n_devices: int) -> tuple[int, int, int, int, int, int]:
        dims = [self.dp, self.fsdp, self.tp, self.sp, self.ep, self.pp]
        fixed = 1
        for d in dims:
            if d != -1:
                fixed *= d
        if n_devices % fixed != 0:
            raise ValueError(f"{n_devices} devices not divisible by fixed axes {fixed}")
        free = n_devices // fixed
        dims = [free if d == -1 else d for d in dims]
        if int(np.prod(dims)) != n_devices:
            raise ValueError(f"mesh {dims} != {n_devices} devices (use one -1 axis)")
        return tuple(dims)


def make_mesh(config: MeshConfig | None = None, devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Build the 6-axis training/rollout mesh.

    Axis order is (dp, fsdp, tp, sp, ep, pp) — tp/ep (the latency-critical
    axes) sit toward the innermost, fastest ICI rings; pipeline stages
    communicate only once per microbatch step so pp tolerates the
    outermost placement.
    """
    devices = list(devices if devices is not None else jax.devices())
    config = config or MeshConfig()
    dims = config.resolve(len(devices))
    dev_array = np.array(devices).reshape(dims)
    return Mesh(dev_array, AXES)


def under(mesh: Mesh | None, fn):
    """``fn`` with every call (so its trace too) made under
    ``jax.set_mesh(mesh)``; ``fn`` itself without a mesh. The trainer and
    the engine put their jitted programs through this: code inside a trace
    that has to go manual over an axis (the MoE block over ``ep``,
    ``blocks._expert_mix_sharded``) finds the mesh there, and every call sets
    it because the mesh is part of jit's cache key."""
    if mesh is None:
        return fn

    @functools.wraps(fn)
    def call(*args, **kwargs):
        with jax.set_mesh(mesh):
            return fn(*args, **kwargs)

    return call


def single_device_mesh(device: jax.Device | None = None) -> Mesh:
    dev = device if device is not None else jax.devices()[0]
    return Mesh(np.array([dev]).reshape(1, 1, 1, 1, 1, 1), AXES)


# -- canonical partition specs --------------------------------------------

# batch-dim sharding for activations/data: batch over (dp, fsdp), seq over sp
BATCH_SPEC = P((DP, FSDP), SP)
# token ids [B, T]
TOKENS_SPEC = P((DP, FSDP), SP)
# logits [B, T, V] — vocab over tp
LOGITS_SPEC = P((DP, FSDP), SP, TP)
REPLICATED = P()


def sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def shard_params(mesh: Mesh, params, specs):
    """device_put a param pytree with per-leaf specs from a matching (or
    partially matching) spec tree: leaves without a spec (e.g. a critic's
    value head absent from ``decoder.param_specs``) fall back to replicated.
    The single shared implementation for actor/critic GSPMD placement.

    Spec lookup is by FLATTENED key path (not dict indexing), so spec trees
    containing pytree nodes without ``__getitem__`` — e.g. quant.QuantWeight
    wrapping (q_spec, scale_spec) — resolve correctly instead of silently
    falling back to replicated."""
    by_path = {
        jax.tree_util.keystr(p): s
        for p, s in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))[0]
    }

    def put(path, x):
        node = by_path.get(jax.tree_util.keystr(path), P())
        if not isinstance(node, P):
            node = P()
        return jax.device_put(x, NamedSharding(mesh, node))

    return jax.tree_util.tree_map_with_path(put, params)


def shard_batch(mesh: Mesh, tree, spec: P = BATCH_SPEC):
    """device_put a pytree of [B, ...] arrays with batch-dim sharding.

    Arrays whose rank is 1 get P((dp, fsdp)); rank ≥2 get ``spec`` truncated
    to their rank.
    """

    def put(x):
        r = np.ndim(x)
        if r == 0:
            return jax.device_put(x, NamedSharding(mesh, P()))
        parts = list(spec)[:r]
        parts += [None] * (r - len(parts))
        return jax.device_put(x, NamedSharding(mesh, P(*parts)))

    return jax.tree_util.tree_map(put, tree)
