"""Data redistribution: reshard live state onto a rebuilt mesh.

This is stage 3 of the paper's malleability pipeline.  The paper defers
transfer-minimizing redistribution to future work; we implement it: the
device order of the new mesh keeps surviving devices in their previous
relative positions (the Eq. 9 reorder guarantees a deterministic order,
and :func:`repro.elastic.runtime.ElasticRuntime` feeds survivors first),
so shards that already sit on a surviving device do not move.

``transfer_stats`` quantifies the win: bytes that stay local vs bytes
that cross devices, for any (old sharding -> new sharding) pair.
``predicted_transfer_stats`` computes the same accounting *without*
materializing any array (from ``Sharding.devices_indices_map``), so the
cost simulator can charge the exact bytes the live reshard will move;
:class:`PytreeBytesModel` packages that as a
``ReconfigEngine.bytes_model``.

:func:`device_put_tree` does the move itself, on the devices.  JAX's
``device_put`` of an array spread over several devices looks, for each
target shard, for a source buffer that holds that shard whole; when one
is missing (a shrink of an FSDP-sharded leaf onto fewer devices) it falls
back to ``x._value``, copying the whole leaf to host memory and uploading
it again, and leaves that host copy cached on the old array.  So such
leaves are first gathered on their own mesh by a jitted identity whose
output is replicated: an all-gather over the devices' links (a
``device_put`` to the replicated sharding would take the same host path).
The copy on a device the target uses is then handed to ``device_put``,
which slices it there and copies the slices device to device.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs


def reshard_tree(tree: Any, mesh: Mesh, spec_tree: Any) -> Any:
    """Reshard every leaf of ``tree`` onto ``mesh`` with the given specs.

    ``spec_tree`` is either a single PartitionSpec applied to all leaves or
    a pytree of specs matching ``tree``'s structure.  Uses
    :func:`device_put_tree`, which moves only the shards that change
    placement, and only between devices.
    """
    if isinstance(spec_tree, P) or spec_tree is None:
        specs = jax.tree.map(lambda _: spec_tree or P(), tree)
    else:
        specs = spec_tree
    return device_put_tree(
        tree, jax.tree.map(lambda _, s: NamedSharding(mesh, s), tree, specs)
    )


def _identity(x: jax.Array) -> jax.Array:
    return x


@functools.lru_cache(maxsize=16)
def _replicate_on(mesh: Mesh):
    """One jitted identity per mesh, its outputs whole on every device."""
    return jax.jit(_identity, out_shardings=NamedSharding(mesh, P()))


def _held_indices(sharding: Any, shape: tuple[int, ...]) -> set[tuple]:
    # The (start, stop) bounds JAX matches shards by (``hashed_index``).
    return {
        tuple((i.start, i.stop) if isinstance(i, slice) else i for i in idx)
        for idx in sharding.devices_indices_map(shape).values()
    }


@functools.lru_cache(maxsize=4096)
def _needs_gather(old: Any, new: Any, shape: tuple[int, ...]) -> bool:
    """Would ``device_put`` from ``old`` onto ``new`` go through the host?

    Yes when the source spans several devices of a mesh and some target
    shard is held whole by none of its buffers.  A single-device source
    is sliced on its device; a source that is no ``NamedSharding`` has no
    mesh to gather on and takes ``device_put`` as it is.
    """
    if old.num_devices == 1 or not isinstance(old, NamedSharding):
        return False
    return not _held_indices(new, shape) <= _held_indices(old, shape)


def _gather_onto(x: jax.Array, target: Any) -> jax.Array:
    """Gather ``x`` whole onto every device of its mesh, wait for it,
    then place the copy on a device ``target`` uses (else its first)
    onto ``target``.  The other copies are dropped on return."""
    whole = _replicate_on(x.sharding.mesh)(x).block_until_ready()
    shards = whole.addressable_shards
    copy = next((s.data for s in shards if s.device in target.device_set),
                shards[0].data)
    return jax.device_put(copy, target)


def device_put_tree(tree: Any, shardings: Any) -> Any:
    """``jax.device_put`` of every leaf of ``tree`` onto the matching leaf
    of ``shardings``, without a copy through host memory.

    A leaf that some old buffer can already supply shard by shard (an
    expansion from one device, a replicated leaf, an unchanged layout)
    goes to ``device_put`` as it is.  Each of the others is gathered on
    its mesh first (the module doc says why) and lands from its gathered
    copy.  One program per leaf, each waited for before the next: in one
    program the leaves' gathers all overlap and each needs a temporary
    as large as its output (the TPU compiler lays out an all-gather on a
    minor dimension apart from the result), which for a 6.9 GB state on
    four v5e chips would fill each chip; with many in flight, the
    devices the target leaves out hold every gathered copy at once, and
    XLA's CPU backend has aborted on a collective's 40 s rendezvous
    timeout (8 host devices under load).

    With :mod:`repro.obs` enabled the gathers are one ``reshard.gather``
    span, and the bytes of the gathered leaves count as
    ``reshard.gathered_bytes`` (0 when none) in the span open around the
    call.

    Args:
        tree: pytree of ``jax.Array``.
        shardings: pytree of ``Sharding`` with ``tree``'s structure.
    Returns:
        The pytree placed by ``shardings``, bit for bit equal to ``tree``.
    """
    leaves, treedef = jax.tree.flatten(tree)
    targets = treedef.flatten_up_to(shardings)
    gather = [i for i, (x, s) in enumerate(zip(leaves, targets))
              if isinstance(x, jax.Array) and _needs_gather(x.sharding, s, x.shape)]
    out = [x if i in gather else jax.device_put(x, s)
           for i, (x, s) in enumerate(zip(leaves, targets))]
    if gather:
        with obs.span("reshard.gather"):
            for i in gather:
                out[i] = _gather_onto(leaves[i], targets[i])
    obs.count("reshard.gathered_bytes", sum(leaves[i].nbytes for i in gather))
    return treedef.unflatten(out)


def _index_key(index: tuple, shape: tuple[int, ...]) -> tuple:
    """Normalize a shard's index slices to ((start, stop), ...) bounds."""
    return tuple(
        (s.start or 0, s.stop if s.stop is not None else dim)
        for s, dim in zip(index, shape)
    )


def _key_nbytes(key: tuple, itemsize: int) -> int:
    return int(np.prod([hi - lo for lo, hi in key]) * itemsize) if key else itemsize


def _shard_index_map(arr: Any) -> dict[tuple, set[int]]:
    """Map shard index-bounds -> device ids currently holding that shard."""
    out: dict[tuple, set[int]] = {}
    for shard in arr.addressable_shards:
        out.setdefault(_index_key(shard.index, arr.shape), set()).add(shard.device.id)
    return out


def _count_transfers(
    old_map: dict[tuple, set[int]],
    new_placements: list[tuple[tuple, int]],
    itemsize: int,
) -> tuple[int, int, int]:
    """(total, stayed, moved) bytes over new (index-key, device-id) pairs."""
    stayed = moved = total = 0
    for key, device_id in new_placements:
        nbytes = _key_nbytes(key, itemsize)
        total += nbytes
        if device_id in old_map.get(key, set()):
            stayed += nbytes
        else:
            moved += nbytes
    return total, stayed, moved


def transfer_stats(old_tree: Any, new_tree: Any) -> dict[str, int]:
    """Measure bytes that moved vs stayed local across a resharding.

    A shard "stays" when the new placement includes a device that already
    held identical index bounds before the reshard.

    Args:
        old_tree: pytree of live arrays before the reshard.
        new_tree: the same pytree after the reshard (matching structure).
    Returns:
        ``{"bytes_total", "bytes_stayed", "bytes_moved"}`` summed over
        all leaves (zeros for an empty tree).
    """
    stayed = moved = total = 0
    old_leaves = jax.tree.leaves(old_tree)
    new_leaves = jax.tree.leaves(new_tree)
    for old, new in zip(old_leaves, new_leaves):
        itemsize = np.dtype(old.dtype).itemsize
        placements = [
            (_index_key(shard.index, new.shape), shard.device.id)
            for shard in new.addressable_shards
        ]
        t, s, m = _count_transfers(_shard_index_map(old), placements, itemsize)
        total += t
        stayed += s
        moved += m
    return {"bytes_total": total, "bytes_stayed": stayed, "bytes_moved": moved}


def predicted_transfer_stats(
    tree: Any, old_shardings: Any, new_shardings: Any
) -> dict[str, int]:
    """Predict :func:`transfer_stats` without materializing any array.

    Uses ``Sharding.devices_indices_map`` on both sides, which is exactly
    the placement ``jax.device_put`` realizes — so for arrays actually
    placed with ``old_shardings``, the prediction equals the measured
    stats of a reshard onto ``new_shardings``, byte for byte.

    Args:
        tree: pytree of shape/dtype carriers (``jax.ShapeDtypeStruct`` or
            arrays; no data is read).
        old_shardings: pytree of ``Sharding`` matching ``tree`` (or a
            single sharding applied to all leaves).
        new_shardings: same, for the target placement.
    Returns:
        ``{"bytes_total", "bytes_stayed", "bytes_moved"}``.
    """
    leaves = jax.tree.leaves(tree)

    def _as_list(shardings, which):
        flat = jax.tree.leaves(
            shardings, is_leaf=lambda x: hasattr(x, "devices_indices_map")
        )
        if len(flat) == 1 and len(leaves) > 1:
            return flat * len(leaves)
        if len(flat) != len(leaves):
            raise ValueError(
                f"{which} shardings have {len(flat)} leaves for a tree of "
                f"{len(leaves)} — bytes would be silently undercounted"
            )
        return flat

    stayed = moved = total = 0
    for leaf, old_s, new_s in zip(leaves, _as_list(old_shardings, "old"),
                                  _as_list(new_shardings, "new")):
        shape = tuple(leaf.shape)
        itemsize = np.dtype(leaf.dtype).itemsize
        old_map: dict[tuple, set[int]] = {}
        for dev, idx in old_s.devices_indices_map(shape).items():
            old_map.setdefault(_index_key(idx, shape), set()).add(dev.id)
        placements = [
            (_index_key(idx, shape), dev.id)
            for dev, idx in new_s.devices_indices_map(shape).items()
        ]
        t, s, m = _count_transfers(old_map, placements, itemsize)
        total += t
        stayed += s
        moved += m
    return {"bytes_total": total, "bytes_stayed": stayed, "bytes_moved": moved}


@dataclass
class PytreeBytesModel:
    """Exact stage-3 bytes model for a live model's parameter pytree.

    Callable as ``(ns_ranks, nt_ranks) -> bytes_moved``, the
    ``ReconfigEngine.bytes_model`` protocol: it resolves the model's
    parameter shardings on 1-D ``("data",)`` meshes of both rank counts
    (devices in pool order, matching
    :meth:`~repro.elastic.runtime.ElasticRuntime.mesh`) and predicts the
    reshard's measured bytes via :func:`predicted_transfer_stats`.

    Requires the host to expose at least ``max(ns, nt)`` devices; rank
    counts are device counts here (one rank per device).
    """

    model: Any                       # repro.models.Model
    devices: Optional[Sequence[Any]] = None   # defaults to jax.devices()
    mode: str = "train"
    _cache: dict = field(default_factory=dict, repr=False)

    def _shardings(self, k: int) -> dict:
        if k not in self._cache:
            from repro.parallel.sharding import (
                ShardingContext,
                param_sharding_abstract,
            )

            devs = list(self.devices if self.devices is not None else jax.devices())
            if k > len(devs):
                raise ValueError(
                    f"PytreeBytesModel needs {k} devices, host has {len(devs)}"
                )
            mesh = Mesh(np.asarray(devs[:k], dtype=object).reshape((k,)), ("data",))
            ctx = ShardingContext(mesh=mesh, mode=self.mode)
            shapes, specs = self._abstract()
            self._cache[k] = param_sharding_abstract(shapes, specs, ctx)
        return self._cache[k]

    def _abstract(self):
        if "abstract" not in self._cache:
            self._cache["abstract"] = self.model.abstract_params()
        return self._cache["abstract"]

    def __call__(self, ns: int, nt: int) -> int:
        return self.stats(ns, nt)["bytes_moved"]

    def total_bytes(self, ranks: int) -> int:
        """Full parameter-pytree bytes — the checkpoint snapshot size.

        Rank-count independent for a replicated-or-sharded pytree (the
        union of shards IS the pytree); the engine's
        :meth:`~repro.core.ReconfigEngine.checkpoint_bytes` calls this
        to size CHECKPOINT/RESTORE events.
        """
        shapes, _ = self._abstract()
        return int(sum(
            int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
            for s in jax.tree.leaves(shapes)
        ))

    def stats(self, ns: int, nt: int) -> dict:
        """Full per-link prediction ``{"bytes_total", "bytes_stayed",
        "bytes_moved"}`` for an ``ns -> nt`` resize — the engine consults
        this (in preference to ``__call__``) so stayed and moved bytes
        are charged against their own link bandwidths."""
        if ns == nt or ns <= 0 or nt <= 0:
            return {"bytes_total": 0, "bytes_stayed": 0, "bytes_moved": 0}
        shapes, _ = self._abstract()
        return predicted_transfer_stats(
            shapes, self._shardings(ns), self._shardings(nt)
        )
