"""Device mesh plumbing: sharded Pages and shard_map execution.

A *sharded page* is a Page pytree whose array leaves carry a leading device
axis: values [ndev, capacity], nulls [ndev, capacity], num_rows [ndev].
Sharding that axis over the mesh gives each device one local Page; operators
run inside `shard_map` on the squeezed local view, and exchanges move rows
between the local views with XLA collectives (shuffle.py).

Reference analogue: a Presto *task* with N parallel drivers connected by
LocalExchange (presto-main-base/.../operator/exchange/LocalExchange.java) —
here the N lanes are TPU chips and the exchange is ICI.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from presto_tpu.data.column import Page

AXIS = "d"


def device_mesh(n_devices: Optional[int] = None,
                devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over the data axis. SQL parallelism is row-partitioning, so
    one axis suffices; ops that need a different distribution reshard over
    it with all_to_all rather than using a second mesh axis."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (AXIS,))


def stack_pages(pages: Sequence[Page]) -> Page:
    """Stack per-device local pages into one sharded page (leading device
    axis). All pages must share capacity, column types and dictionaries."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *pages)


def unstack_page(stacked: Page) -> List[Page]:
    """Split a sharded page into per-device host-side pages. Transfers to
    host first: eager slicing of a sharded device array re-dispatches an
    XLA program per access (and aborts on some backends); result
    consumption is a host concern anyway."""
    host = jax.device_get(stacked)
    ndev = host.num_rows.shape[0]
    return [jax.tree_util.tree_map(lambda x: x[i], host)
            for i in range(ndev)]


def _squeeze(tree):
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def _expand(tree):
    return jax.tree_util.tree_map(lambda x: x[None], tree)


def run_sharded(mesh: Mesh, fn: Callable, *stacked_args,
                replicated_out: bool = False, with_needed: bool = False):
    """Run `fn(local_page, ...)` under shard_map over `mesh`.

    Each stacked arg is sharded on its leading axis; inside, fn sees the
    squeezed local view (arrays without the device axis) and may call the
    collectives in shuffle.py over axis "d".

    Output contracts:
      default            fn returns a local page       -> stacked page
      replicated_out     fn returns a replicated value -> value as-is
      with_needed        fn returns (local page, replicated needed-tuple)
                         -> (stacked page, needed-tuple); used by the
                         overflow-retry protocol (dist.py).
    """
    def wrapper(*blocks):
        out = fn(*[_squeeze(b) for b in blocks])
        if with_needed:
            page, needed = out
            return _expand(page), needed
        return out if replicated_out else _expand(out)

    if with_needed:
        out_specs = (P(AXIS), P())
    elif replicated_out:
        out_specs = P()
    else:
        out_specs = P(AXIS)
    shmapped = jax.shard_map(
        wrapper, mesh=mesh,
        in_specs=tuple(P(AXIS) for _ in stacked_args),
        out_specs=out_specs,
        check_vma=False)
    return shmapped(*stacked_args)
