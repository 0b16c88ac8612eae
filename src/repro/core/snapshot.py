"""Device→host snapshots of sharded JAX pytrees.

This is the bridge between a JAX application's ``TrainState`` and iCheck's
byte-oriented agents: every pytree leaf becomes a *region* whose parts are
the distinct device shards (deduplicated across replicas).  The device→host
copy is issued asynchronously for all leaves first (``copy_to_host_async`` —
the TPU DMA analogue of the paper's RDMA source buffers) and only then
gathered, so device compute can proceed underneath.

With ``codec="q8"`` / ``codec="q8-delta"`` the encode runs **on device**
before the D2H copy: each float region part goes through
``kernels/ckpt_codec.quantize`` (or ``quantize_delta`` against the
catalog's previous-codes state from ``chain_lookup``), so the host pulls
int8 codes + 1/256 overhead of f32 scales — ~4x fewer D2H bytes than the
raw f32 leaves — and the resulting :class:`~repro.core.tiers.EncodedRegion`
frames travel the client→agent fabric and the storage tiers as-is
(``ICheckClient.commit_snapshot``).

Given a ``tracer`` (the cluster's :class:`~repro.obs.TraceCollector`), a
snapshot is a ``snapshot`` span with one child per phase and region —
``snapshot/encode`` (kernel launch and async copy), ``snapshot/d2h_wait``,
``snapshot/xor``, ``snapshot/frame`` — and a restore's placement is
``restore/assemble`` and ``restore/h2d`` per leaf.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import plan as planlib
from ..kernels.ckpt_codec.blocks import BLOCK
from ..obs import TraceCollector
from .tiers import (DeltaState, EncodedRegion, is_float_dtype, pack_q8_region,
                    q8_pack_full)
from .types import PartitionDesc, PartitionScheme, RegionMeta

TRACK = "snapshot"


def _leaf_name(path) -> str:
    import jax

    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        elif isinstance(p, jax.tree_util.GetAttrKey):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts) or "leaf"


@dataclasses.dataclass
class SnapshotRegion:
    meta: RegionMeta
    parts: Dict[int, np.ndarray]          # part index -> host array (local shard)
    boxes: Tuple[planlib.Box, ...]        # global boxes, canonical order
    # device-encoded wire frames (q8 / q8-delta); when set, ``parts`` is
    # empty — the raw f32 payload never crossed the D2H link
    encoded: Optional[EncodedRegion] = None


@dataclasses.dataclass
class HostSnapshot:
    regions: Dict[str, SnapshotRegion]
    step: int = 0
    # the traced ``snapshot`` span (None untraced); a commit of this
    # snapshot adopts it into the checkpoint's trace tree
    trace: Any = None

    def total_bytes(self) -> int:
        """Bytes held on the host (raw parts + encoded wire frames)."""
        total = 0
        for r in self.regions.values():
            total += sum(p.nbytes for p in r.parts.values())
            if r.encoded is not None:
                total += sum(len(b) for b in r.encoded.blobs.values())
        return total


def leaf_names(tree) -> List[str]:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [_leaf_name(path) for path, _ in flat]


def _device_parts(leaf) -> Tuple[Tuple[planlib.Box, ...], Dict[int, Any],
                                 PartitionDesc]:
    """Distinct device shards of one leaf (replicas deduplicated), without
    forcing a host copy: part index -> device (or numpy) array."""
    arr = leaf
    if not hasattr(arr, "addressable_shards"):
        arr = np.asarray(arr)
    if isinstance(arr, np.ndarray):
        boxes = (tuple((0, s) for s in arr.shape),)
        parts: Dict[int, Any] = {0: arr}
        desc = PartitionDesc(scheme=PartitionScheme.MESH, num_parts=1,
                             bounds=boxes)
        return boxes, parts, desc
    shape = tuple(arr.shape)
    boxes = planlib.mesh_part_bounds(shape, arr.sharding)
    box_index = {b: i for i, b in enumerate(boxes)}
    parts = {}
    for sh in arr.addressable_shards:
        box = []
        for d, sl in enumerate(sh.index):
            lo = 0 if sl.start is None else int(sl.start)
            hi = shape[d] if sl.stop is None else int(sl.stop)
            box.append((lo, hi))
        idx = box_index[tuple(box)]
        if idx not in parts:                       # skip replicas
            parts[idx] = sh.data
    desc = PartitionDesc(scheme=PartitionScheme.MESH,
                         num_parts=len(boxes), bounds=boxes)
    return boxes, parts, desc


def _chain_states(chain_lookup, name: str, num_parts: int,
                  part_sizes: Dict[int, int]):
    """Previous-codes state usable for a device-side delta encode of this
    region, or (None, None) when the next frame must be a keyframe."""
    if chain_lookup is None:
        return None, None
    rc = chain_lookup(name, num_parts)
    if rc is None:
        return None, None
    prev: Dict[int, DeltaState] = dict(rc.parts)
    for p, n in part_sizes.items():
        st = prev.get(p)
        nb = -(-max(n, 1) // BLOCK)
        if st is None or st.n != n or st.codes.shape[0] != nb:
            return None, None
    return prev, tuple(rc.chain)


def snapshot_pytree(tree, step: int = 0, codec: str = "raw",
                    chain_lookup=None, impl: Optional[str] = None,
                    tracer: Optional[TraceCollector] = None
                    ) -> HostSnapshot:
    """Snapshot a pytree of (possibly sharded) jax.Arrays to host memory.

    ``codec="q8"`` / ``"q8-delta"``: float leaves are quantized on device
    (``kernels/ckpt_codec``) before the D2H copy; ``chain_lookup(name,
    num_parts)`` supplies the catalog's previous-codes state so ``q8-delta``
    regions ship sparse XOR-delta frames (``ICheckClient.delta_chain_lookup``
    is the intended callable).  Non-float leaves always travel raw.

    Traced, the ``snapshot`` span nests in the caller's current span, or
    else opens a provisional trace that the commit of this snapshot adopts
    (``HostSnapshot.trace``).
    """
    import jax

    tracer = tracer if tracer is not None else TraceCollector()
    cur = tracer.current()
    trace_id = cur.trace_id if cur is not None \
        else tracer.provisional(TRACK)
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    with tracer.span("snapshot", trace_id, TRACK, root=cur is None,
                     step=step, codec=codec, regions=len(flat)) as ctx:
        regions = _snapshot_leaves(flat, codec, chain_lookup, impl, tracer,
                                   trace_id)
    return HostSnapshot(regions=regions, step=step, trace=ctx)


def _snapshot_leaves(flat, codec: str, chain_lookup, impl: Optional[str],
                     tracer: TraceCollector, trace_id: Optional[str]
                     ) -> Dict[str, SnapshotRegion]:
    encode = codec in ("q8", "q8-delta")
    if encode:
        from ..kernels.ckpt_codec import quantize, quantize_delta

    # 1) kick all async D2H copies; for encoded leaves, launch the device
    #    quantize first and async-copy its (int8, f32/256) outputs instead
    #    of the raw leaf
    work: Dict[str, dict] = {}
    for path, leaf in flat:
        name = _leaf_name(path)
        leaf_dtype = getattr(leaf, "dtype", None)
        if leaf_dtype is None:
            leaf_dtype = np.asarray(leaf).dtype
        if encode and is_float_dtype(leaf_dtype):
            with tracer.timed("snapshot/encode", trace_id, TRACK,
                              region=name) as launch:
                boxes, parts, desc = _device_parts(leaf)
                sizes = {p: int(np.prod(np.shape(a)) or 1)
                         for p, a in parts.items()}
                prev = parent_chain = None
                if codec == "q8-delta":
                    prev, parent_chain = _chain_states(
                        chain_lookup, name, desc.num_parts, sizes)
                outs = {}
                for p, a in parts.items():
                    if prev is not None:
                        prev_q = prev[p].codes_dev
                        if prev_q is None:
                            prev_q = prev[p].codes
                        d, s, q = quantize_delta(a, prev_q, impl=impl)
                        # the dense int8 XOR delta + scales cross D2H (~1/4
                        # of the f32 bytes; sparsification happens
                        # host-side); the new full codes q stay
                        # device-resident for the next commit so nothing is
                        # uploaded back
                        outs[p] = (d, s, q)
                    else:
                        q, s = quantize(a, impl=impl)
                        outs[p] = (q, s, q)
                for d_or_q, s, _ in outs.values():
                    for out in (d_or_q, s):
                        if hasattr(out, "copy_to_host_async"):
                            out.copy_to_host_async()
            work[name] = {"boxes": boxes, "desc": desc, "sizes": sizes,
                          "outs": outs, "prev": prev,
                          "parent_chain": parent_chain,
                          "launch_s": launch.seconds}
        elif hasattr(leaf, "copy_to_host_async"):
            leaf.copy_to_host_async()
    # 2) gather per-shard host arrays / pack the encoded wire frames
    regions: Dict[str, SnapshotRegion] = {}
    for path, leaf in flat:
        name = _leaf_name(path)
        if name in work:
            regions[name] = _gather_encoded(name, leaf, codec, work[name],
                                            tracer, trace_id)
            continue
        boxes, dev_parts, desc = _device_parts(leaf)
        with tracer.span("snapshot/d2h_wait", trace_id, TRACK, region=name):
            parts = {p: np.asarray(a) for p, a in dev_parts.items()}
            nbytes = sum(p.nbytes for p in parts.values())
            tracer.note(bytes=nbytes)
        np_dtype = parts[0].dtype if parts else np.dtype("float32")
        meta = RegionMeta(name=name, shape=tuple(np.shape(leaf)),
                          dtype=str(np_dtype),
                          partition=desc, nbytes=nbytes)
        regions[name] = SnapshotRegion(meta=meta, parts=parts, boxes=boxes)
    return regions


def _gather_encoded(name: str, leaf, codec: str, w: dict,
                    tracer: TraceCollector, trace_id: Optional[str]
                    ) -> SnapshotRegion:
    """Finish one device-encoded region: D2H the codes/scales, reconstruct
    codes from deltas (host XOR), frame via the shared packer."""
    prev: Optional[Dict[int, DeltaState]] = w["prev"]
    with tracer.timed("snapshot/d2h_wait", trace_id, TRACK,
                      region=name) as wait:
        host = {p: (np.asarray(d_or_q),
                    np.asarray(s).astype(np.float32, copy=False))
                for p, (d_or_q, s, _) in w["outs"].items()}
        tracer.note(bytes=sum(a.nbytes + s.nbytes
                              for a, s in host.values()))
    seconds = w["launch_s"] + wait.seconds
    qparts: Dict[int, Tuple[int, np.ndarray, np.ndarray]] = {}
    dense_deltas: Dict[int, np.ndarray] = {}
    if prev is not None:
        # the kernel shipped the XOR delta; reconstruct the full codes
        # from the host-side previous codes (one int8 XOR — the packer
        # reuses the dense delta instead of re-deriving it)
        with tracer.timed("snapshot/xor", trace_id, TRACK,
                          region=name) as xor:
            for p, (a, scales) in host.items():
                qparts[p] = (w["sizes"][p], np.bitwise_xor(prev[p].codes, a),
                             scales)
                dense_deltas[p] = a
            tracer.note(bytes=sum(a.nbytes for a in dense_deltas.values()))
        seconds += xor.seconds
    else:
        qparts = {p: (w["sizes"][p], a, scales)
                  for p, (a, scales) in host.items()}
    np_dtype = getattr(leaf, "dtype", None)
    np_dtype = np.dtype(np_dtype) if np_dtype is not None \
        else np.asarray(leaf).dtype
    raw_nbytes = sum(n * np_dtype.itemsize for n, _, _ in qparts.values())
    with tracer.timed("snapshot/frame", trace_id, TRACK,
                      region=name) as framing:
        if codec == "q8-delta":
            packed: Dict[str, bool] = {}
            blobs, states, frame = pack_q8_region(
                qparts, prev, deltas=dense_deltas or None, info=packed)
            built = packed["delta_built"]
            tracer.note(frame=frame, delta_built=built,
                        delta_discarded=built and frame == "key")
            for p, (_, _, q_dev) in w["outs"].items():
                states[p].codes_dev = q_dev
        else:
            blobs = {p: q8_pack_full(n, codes, scales)
                     for p, (n, codes, scales) in qparts.items()}
            states = frame = None
            tracer.note(frame="q8")
        tracer.note(bytes=sum(len(b) for b in blobs.values()))
    enc = EncodedRegion(codec=codec, blobs=blobs, states=states, frame=frame,
                        raw_nbytes=raw_nbytes,
                        parent_chain=w["parent_chain"],
                        encode_s=seconds + framing.seconds)
    meta = RegionMeta(name=name, shape=tuple(np.shape(leaf)),
                      dtype=str(np_dtype), partition=w["desc"],
                      nbytes=raw_nbytes, codec=codec)
    return SnapshotRegion(meta=meta, parts={}, boxes=w["boxes"], encoded=enc)


def restore_pytree(template, regions: Dict[str, Dict[int, np.ndarray]],
                   region_meta: Dict[str, RegionMeta],
                   shardings: Optional[Dict[str, Any]] = None,
                   tracer: Optional[TraceCollector] = None):
    """Rebuild a pytree of jax.Arrays from fetched region parts.

    ``template`` provides structure + avals (e.g. from ``jax.eval_shape``);
    ``shardings`` maps leaf name → target Sharding (None → commit to default
    device layout).  Parts may come from a *different* partitioning than the
    target: they are reassembled via their recorded boxes and re-split by
    ``device_put`` — the caller can instead use ``ICheckClient.redistribute``
    to move only the needed slices.  Traced, each leaf is a
    ``restore/assemble`` span and a ``restore/h2d`` span (the ``device_put``
    call; the transfer itself shows on the device trace), nested in the
    caller's current span.
    """
    import jax

    tracer = tracer if tracer is not None else TraceCollector()
    cur = tracer.current()
    trace_id = cur.trace_id if cur is not None else None
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in flat:
        name = _leaf_name(path)
        meta = region_meta[name]
        parts = regions[name]
        with tracer.span("restore/assemble", trace_id, TRACK, region=name):
            if meta.partition.scheme == PartitionScheme.MESH:
                boxes = meta.partition.bounds
                full = np.empty(meta.shape, dtype=np.dtype(meta.dtype))
                for idx, part in parts.items():
                    dsl = tuple(slice(lo, hi) for lo, hi in boxes[idx])
                    full[dsl] = part.reshape([hi - lo
                                              for lo, hi in boxes[idx]])
            else:
                ordered = [parts[i] for i in range(meta.partition.num_parts)]
                full = planlib.assemble_array(ordered, meta.partition,
                                              meta.shape)
            target_dtype = getattr(leaf, "dtype", full.dtype)
            full = full.astype(target_dtype, copy=False)
            tracer.note(bytes=full.nbytes)
        sharding = (shardings or {}).get(name)
        with tracer.span("restore/h2d", trace_id, TRACK, region=name,
                         bytes=full.nbytes):
            if sharding is not None:
                leaves.append(jax.device_put(full, sharding))
            else:
                leaves.append(jax.device_put(full))
    return jax.tree_util.tree_unflatten(treedef, leaves)
