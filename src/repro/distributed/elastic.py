"""Elastic scaling: rebuild the mesh from the devices that are actually
alive (DESIGN.md §4, §12).

**What the classifier serving engine uses** (launch/serving_engine.py,
since the PR that grew serve_classifier into the async driver):
``bank_pool_mesh`` — the serving ``DevicePool`` calls it after a
simulated device loss to re-mesh the design bank over the survivors
(shrinking the bank shard, down to unsharded single-device serving when
one device remains), after which the bit-for-bit served==exported parity
contract is re-asserted before serving resumes.

**What remains dormant** (LM-training substrate, exercised only by its
own tests): ``plan_mesh`` / ``make_elastic_mesh`` implement the
TP-pinned (pod, data, model) degradation policy for large pod jobs, and
``reshard_state`` restores a checkpoint against the shrunken mesh. The
classifier bank has no TP axis, so serving deliberately does not reuse
that policy.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax

from repro.launch import mesh as mesh_lib


def bank_pool_mesh(devices: Sequence):
    """1-axis ('data',) mesh over an explicit list of surviving devices —
    the serving engine's re-shard target. The design-bank population
    rules (distributed/sharding.design_bank_axes) partition the bank's D
    axis over 'data' when it divides, else fall back to replicated; the
    same divisibility contract a fresh mesh gets, applied to survivors.

    Built with ``Mesh`` over the devices in the order given, not with
    ``jax.make_mesh``: on TPU the latter maps the list onto the physical
    torus and refuses a set of survivors that no longer fills it (3 of a
    2x2)."""
    import numpy as np
    from jax.sharding import Mesh
    devices = list(devices)
    if not devices:
        raise ValueError("bank_pool_mesh needs at least one device")
    return Mesh(np.array(devices).reshape(len(devices), 1), ("data", "model"))


def plan_mesh(n_devices: int, *, model: int = 16, chips_per_pod: int = 256):
    """Largest (pod, data, model) grid using <= n_devices devices. The pod
    count follows physical pods (256 chips each); capacity loss inside a
    pod shrinks 'data'; TP degrades last (to a power of two) only when
    fewer than `model` devices survive."""
    if n_devices < model:
        m = 1
        while m * 2 <= n_devices:
            m *= 2
        return (1, max(n_devices // m, 1), m)
    rest = n_devices // model
    pods = max(n_devices // chips_per_pod, 1)
    while pods > 1 and rest % pods:
        pods -= 1
    return (pods, rest // pods, model)


def make_elastic_mesh(devices: Optional[Sequence] = None, *, model: int = 16):
    """Mesh over surviving devices. Drops remainder devices that don't fill
    the grid (they rejoin at the next restart boundary)."""
    devices = list(devices if devices is not None else jax.devices())
    pods, data, tp = plan_mesh(len(devices), model=model)
    n = pods * data * tp
    import numpy as np
    arr = np.array(devices[:n]).reshape(
        (pods, data, tp) if pods > 1 else (data, tp))
    axes = ("pod", "data", "model") if pods > 1 else ("data", "model")
    from jax.sharding import Mesh
    return Mesh(arr, axes)


def reshard_state(ckpt, step: int, state_like, new_mesh, cfg):
    """Restore a checkpoint against a NEW mesh (device count changed)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed import sharding as sh
    pshard = sh.param_shardings(state_like.params, new_mesh, cfg)
    rep = NamedSharding(new_mesh, P())
    opt_sh = type(state_like.opt)(step=rep, m=pshard, v=pshard)
    shardings = type(state_like)(params=pshard, opt=opt_sh, err=None)
    return ckpt.restore(step, state_like, shardings)
