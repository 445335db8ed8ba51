"""Production meshes.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init, and smoke tests
must see 1 CPU device while the dry-run sees 512 placeholders).
"""
from __future__ import annotations

import warnings

import jax


def _client_axis_size(num_clients: int, slots: int) -> int:
    """Size of the 'client' mesh axis given `slots` devices available to it.

    The largest axis that both divides the slot count (the mesh must tile
    the devices) and divides J (every shard holds the same number of WHOLE
    encoders — a lopsided split would leave ragged stacks shard_map cannot
    express): J itself when it divides the slots, a partial-parallel axis
    (several nodes per shard) otherwise, and a replicated axis (size 1,
    with a warning) when no common divisor exists."""
    if num_clients >= 1 and slots % num_clients == 0:
        return num_clients
    client = max((k for k in range(1, min(num_clients, slots) + 1)
                  if slots % k == 0 and num_clients % k == 0), default=1)
    if client == 1 and num_clients > 1:
        warnings.warn(
            f"J={num_clients} clients share no divisor with the {slots} "
            f"available device slots; falling back to a replicated client "
            f"axis (client=1) — node-parallel INL/FL execution is "
            f"disabled, batch/data parallelism still applies.",
            stacklevel=3)
    return client


def current_abstract_mesh():
    """The ambient abstract mesh, or None when no mesh is active."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_inl_mesh(num_clients: int, *, multi_pod: bool = False):
    """Mesh for the paper-mode (INL) trainer: a 'client' axis holds the J
    edge nodes; remaining capacity goes to data/model parallelism.
    256 (or 512) chips total, same hardware as make_production_mesh.

    When J does not divide the per-model-group chip count the client axis
    falls back to replicated (size 1, with a warning) instead of erroring —
    the scheme still runs, data-parallel only."""
    model = 16
    total = 512 if multi_pod else 256
    client = _client_axis_size(num_clients, total // model)
    data = total // (client * model)
    return jax.make_mesh((client, data, model),
                         ("client", "data", "model"))


def make_inl_host_mesh(num_clients: int):
    """INL mesh over the locally visible devices (CPU smoke / forced
    multi-device runs): ('client', 'data') with the J nodes on 'client' when
    J divides the device count, else a replicated client axis (warned) and
    everything on 'data'.  This is the mesh `schemes.runner.run_scheme`
    takes for sharded host execution."""
    n = len(jax.devices())
    client = _client_axis_size(num_clients, n)
    return jax.make_mesh((client, n // client), ("client", "data"))


def make_host_mesh():
    """Whatever devices exist locally (CPU smoke runs): 1D 'data' mesh."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",))


def data_axes(mesh) -> tuple:
    """The batch-sharding axes for a mesh (everything that isn't 'model' or
    'client')."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
