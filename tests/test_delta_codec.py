"""Incremental (q8-delta) checkpointing: wire codec, chain lifecycle,
dtype sweep, device-side snapshot encode, and telemetry gauges."""
import dataclasses

import numpy as np
import pytest

from repro.core import ICheckClient, ICheckCluster
from repro.core import events as E
from repro.core.tiers import (decode_payload, encode_delta_region,
                              encode_payload, q8_chain_decode, resolve_codec)
from repro.core.types import RestoreError, ShardKey

FLOAT_DTYPES = ("float32", "float16", "bfloat16")


def _parts(data, n):
    return {i: p for i, p in enumerate(np.array_split(data, n))}


def _events(cluster):
    return [e["event"] for e in cluster.controller.events]


def _f32(x):
    return np.asarray(x).astype(np.float32)


# ================================================================ wire codec
def test_resolve_codec_accepts_q8_delta():
    assert resolve_codec("q8-delta") == "q8-delta"


@pytest.mark.parametrize("dtype", FLOAT_DTYPES)
@pytest.mark.parametrize("codec", ["q8", "q8-delta"])
def test_codec_dtype_roundtrip(codec, dtype):
    """q8 and q8-delta keyframes round-trip f32/f16/bf16 within the
    blockwise quantization error bound."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal(1000).astype(np.dtype(dtype))
    blob = encode_payload(x.tobytes(), codec, dtype)
    y = np.frombuffer(decode_payload(blob, codec, dtype), np.dtype(dtype))
    err = np.abs(_f32(y) - _f32(x)).max()
    # per-block error <= absmax/127 * 0.5 + one target-dtype rounding step
    assert err <= np.abs(_f32(x)).max() / 127 * 0.51 + 0.01


def test_delta_chain_encode_decode_sparse():
    """Low-churn deltas pack only the changed blocks; replay is
    bit-identical to decoding a full q8 frame of the final data."""
    rng = np.random.default_rng(1)
    x0 = rng.standard_normal(6000).astype(np.float32)
    b0, s0, f0 = encode_delta_region({0: x0.tobytes()}, "float32", None)
    assert f0 == "key"
    x1 = x0.copy()
    x1[:8] += 1.0                          # touches one 256-value block
    b1, s1, f1 = encode_delta_region({0: x1.tobytes()}, "float32", s0)
    assert f1 == "delta"
    assert len(b1[0]) < len(b0[0]) / 10    # sparse: near-zero wire bytes
    out = np.frombuffer(q8_chain_decode([b0[0], b1[0]], "float32"),
                        np.float32)
    full = np.frombuffer(
        decode_payload(encode_payload(x1.tobytes(), "q8", "float32"),
                       "q8", "float32"), np.float32)
    np.testing.assert_array_equal(out, full)


def test_delta_never_loses_to_q8_on_high_churn():
    """A full-churn commit falls back to a keyframe (same bytes as q8)
    instead of paying the sparse-index overhead."""
    rng = np.random.default_rng(2)
    x0 = rng.standard_normal(6000).astype(np.float32)
    _, s0, _ = encode_delta_region({0: x0.tobytes()}, "float32", None)
    x1 = rng.standard_normal(6000).astype(np.float32)
    b1, _, f1 = encode_delta_region({0: x1.tobytes()}, "float32", s0)
    q8_blob = encode_payload(x1.tobytes(), "q8", "float32")
    assert f1 == "key"
    assert len(b1[0]) == len(q8_blob)


def test_delta_frame_alone_raises():
    rng = np.random.default_rng(3)
    x0 = rng.standard_normal(600).astype(np.float32)
    _, s0, _ = encode_delta_region({0: x0.tobytes()}, "float32", None)
    x1 = x0.copy()
    x1[0] += 1
    b1, _, f1 = encode_delta_region({0: x1.tobytes()}, "float32", s0)
    assert f1 == "delta"
    with pytest.raises(RestoreError):
        decode_payload(b1[0], "q8-delta", "float32")
    with pytest.raises(RestoreError):
        q8_chain_decode([b1[0]], "float32")


def test_corrupt_frame_raises_restore_error():
    with pytest.raises(RestoreError):
        q8_chain_decode([b"X" * 32], "float32")
    rng = np.random.default_rng(4)
    x = rng.standard_normal(600).astype(np.float32)
    blob = encode_payload(x.tobytes(), "q8-delta", "float32")
    with pytest.raises(RestoreError):
        q8_chain_decode([blob[:-7]], "float32")     # truncated keyframe


def test_chain_replay_matches_undelta_dequantize():
    """The host replay (q8_chain_decode) and the device replay primitive
    (kernels undelta_dequantize) produce bit-identical restores."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core.tiers import DeltaState, pack_q8_region, q8_pack_full
    from repro.kernels.ckpt_codec import quantize, undelta_dequantize
    from repro.kernels.ckpt_codec.blocks import BLOCK

    rng = np.random.default_rng(6)
    n = 1500
    x0 = rng.standard_normal(n).astype(np.float32)
    x1 = x0.copy()
    x1[:BLOCK // 2] += 0.5
    q0, s0 = (np.asarray(v) for v in quantize(x0, impl="xla"))
    q1, s1 = (np.asarray(v) for v in quantize(x1, impl="xla"))
    key = q8_pack_full(n, q0, s0, b"K")
    blobs, _, frame = pack_q8_region(
        {0: (n, q1, s1)}, {0: DeltaState(n=n, codes=q0, scales=s0)})
    assert frame == "delta"
    delta = blobs[0]
    host = np.frombuffer(q8_chain_decode([key, delta], "float32"),
                         np.float32)
    dense_delta = np.bitwise_xor(q1, q0)
    device = np.asarray(undelta_dequantize(
        jnp.asarray(dense_delta), jnp.asarray(q0), jnp.asarray(s1), (n,),
        jnp.float32, impl="xla"))
    np.testing.assert_array_equal(host, device)


# The packer as it stood before it counted changed blocks first: it built
# every delta frame, compared sizes and threw the loser away.  Kept
# verbatim as the oracle the counting packer must match byte for byte.
_REF_Q8_QUANT = b"Q"
_REF_Q8_KEY = b"K"
_REF_Q8_DELTA = b"D"


def _ref_q8_pack_full(n, codes, scales, mode=_REF_Q8_QUANT):
    return (mode + int(n).to_bytes(8, "little")
            + np.ascontiguousarray(scales, np.float32).tobytes()
            + np.ascontiguousarray(codes, np.int8).tobytes())


def _ref_q8_full_size(nb):
    return 9 + 4 * nb + 256 * nb


def _ref_q8_pack_delta(n, codes, scales, prev, delta=None):
    if prev.n != n or prev.codes.shape != codes.shape:
        return None
    if delta is None:
        delta = np.bitwise_xor(codes, prev.codes)
    changed = np.logical_or((delta != 0).any(axis=1),
                            (scales != prev.scales).any(axis=1))
    idx = np.flatnonzero(changed).astype(np.uint32)
    return (_REF_Q8_DELTA + int(n).to_bytes(8, "little")
            + len(idx).to_bytes(4, "little") + idx.tobytes()
            + np.ascontiguousarray(scales[idx], np.float32).tobytes()
            + np.ascontiguousarray(delta[idx], np.int8).tobytes())


def _ref_pack_q8_region(parts, prev, deltas=None, info=None):
    from repro.core.tiers import DeltaState

    states = {p: DeltaState(n=n, codes=codes, scales=scales)
              for p, (n, codes, scales) in parts.items()}
    if info is not None:
        info["delta_built"] = False
    if prev is not None and set(prev) == set(parts):
        delta_blobs = {}
        for p, (n, codes, scales) in parts.items():
            blob = _ref_q8_pack_delta(n, codes, scales, prev[p],
                                      delta=(deltas or {}).get(p))
            if blob is None:
                break
            delta_blobs[p] = blob
        if len(delta_blobs) == len(parts):
            if info is not None:
                info["delta_built"] = True
            key_total = sum(_ref_q8_full_size(codes.shape[0])
                            for _, codes, _ in parts.values())
            if sum(len(b) for b in delta_blobs.values()) < key_total:
                return delta_blobs, states, "delta"
    keys = {p: _ref_q8_pack_full(n, codes, scales, _REF_Q8_KEY)
            for p, (n, codes, scales) in parts.items()}
    return keys, states, "key"


_NB = 100                       # blocks of the churned part; its last is
_N = _NB * 256 - 37             # padded
# the last changed-block count whose delta (13 + 264 nnz bytes) is still
# strictly smaller than the keyframe (9 + 260 nb bytes), and the first not
_LAST_DELTA = max(k for k in range(_NB + 1)
                  if 13 + 264 * k < 9 + 260 * _NB)


def _churn(x, blocks, how="add"):
    """Change the given 256-value blocks of ``x``: ``add`` moves a value
    (its codes change), ``double`` scales the block by 2 (same codes, a new
    scale)."""
    y = x.copy()
    for b in blocks:
        if how == "add":
            y[b * 256] += 3.0
        else:
            y[b * 256:(b + 1) * 256] *= 2
    return y


@pytest.mark.parametrize("case,changed,frame", [
    ("zero", 0, "delta"),
    ("one", 1, "delta"),
    ("half", _NB // 2, "delta"),
    ("last_delta", _LAST_DELTA, "delta"),
    ("first_key", _LAST_DELTA + 1, "key"),
    ("all", _NB, "key"),
    ("scale_only", _NB // 2, "delta"),
    ("part_mismatch", None, "key"),
])
def test_counting_packer_matches_reference_packer(case, changed, frame):
    """Counting changed blocks first and building only the frame that
    ships gives the reference packer's blobs, states and frame at every
    churn level, through the packer and through the host encode."""
    from repro.core.tiers import pack_q8_region, q8_quantize_part

    assert 13 + 264 * _LAST_DELTA < 9 + 260 * _NB \
        <= 13 + 264 * (_LAST_DELTA + 1)
    rng = np.random.default_rng(21)
    x0 = {0: rng.standard_normal(_N).astype(np.float32)}
    if case == "part_mismatch":
        # a second part, another size than in the previous commit
        x0[1] = rng.standard_normal(700).astype(np.float32)
        x1 = {0: _churn(x0[0], [3]),
              1: rng.standard_normal(1200).astype(np.float32)}
    else:
        x1 = {0: _churn(x0[0], range(changed),
                        "double" if case == "scale_only" else "add")}
    _, prev, _ = encode_delta_region({p: x.tobytes() for p, x in x0.items()},
                                     "float32", None)
    parts = {p: q8_quantize_part(x.tobytes(), "float32")
             for p, x in x1.items()}
    if case == "scale_only":
        np.testing.assert_array_equal(parts[0][1], prev[0].codes)
        assert (parts[0][2] != prev[0].scales).sum() == changed
    want_blobs, want_states, want_frame = _ref_pack_q8_region(parts, prev)
    assert want_frame == frame
    info = {}
    got = {"packer": pack_q8_region(parts, prev, info=info),
           "host encode": encode_delta_region(
               {p: x.tobytes() for p, x in x1.items()}, "float32", prev)}
    for caller, (blobs, states, got_frame) in got.items():
        assert got_frame == want_frame, caller
        assert blobs == want_blobs, caller
        assert all(type(b) is bytes for b in blobs.values())
        assert set(states) == set(want_states)
        for p, st in states.items():
            assert st.n == want_states[p].n, caller
            np.testing.assert_array_equal(st.codes, want_states[p].codes)
            np.testing.assert_array_equal(st.scales, want_states[p].scales)
    assert info["blocks"] == _NB + (5 if case == "part_mismatch" else 0)
    assert info["changed_blocks"] == changed
    assert info["delta_built"] == (frame == "delta")


def test_shared_block_reference_matches_kernels():
    """The host wire codec and the jnp oracle share one blockwise math
    (the dedup satellite): codes and scales must agree exactly."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.ckpt_codec.blocks import quantize_np, to_blocks_np
    from repro.kernels.ckpt_codec.ref import quantize_ref

    rng = np.random.default_rng(5)
    x = rng.standard_normal(1234).astype(np.float32) * 13
    blocks, _ = to_blocks_np(x)
    q_np, s_np = quantize_np(blocks)
    q_j, s_j = quantize_ref(jnp.asarray(blocks))
    np.testing.assert_array_equal(q_np, np.asarray(q_j))
    np.testing.assert_array_equal(s_np, np.asarray(s_j))


# ========================================================== chain lifecycle
@pytest.fixture()
def cluster(tmp_path):
    c = ICheckCluster(n_icheck_nodes=2, n_spare_nodes=2,
                      node_memory=256 << 20, pfs_root=str(tmp_path / "pfs"),
                      adaptive_interval=False)
    yield c
    c.close()


def _delta_client(cluster, ranks=4, keyframe_every=8, **kw):
    return ICheckClient("app", cluster.controller, ranks=ranks,
                        codec="q8-delta", keyframe_every=keyframe_every,
                        **kw).init()


def test_keyframe_every_k_and_replay_bit_identical(cluster):
    """Keyframe cadence follows keyframe_every; a restart that replays
    keyframe + deltas equals a plain-q8 restore of the same data bit for
    bit."""
    client = _delta_client(cluster, keyframe_every=3)
    rng = np.random.default_rng(0)
    data = rng.standard_normal((64, 8)).astype(np.float32)
    client.add_adapt("x", data.shape, "float32", num_parts=4)
    frames = []
    for step in range(5):
        data[step] += 1.0                   # low churn
        h = client.commit(step, {"x": _parts(data, 4)}, blocking=True,
                          drain=False)
        frames.append(h.meta.regions["x"].frame)
    assert frames == ["key", "delta", "delta", "key", "delta"]
    assert h.meta.regions["x"].chain == (3, 4)

    meta, out, _ = client.restart()
    assert meta.step == 4
    for part, arr in _parts(data, 4).items():
        full = np.frombuffer(
            decode_payload(encode_payload(arr.tobytes(), "q8", "float32"),
                           "q8", "float32"), np.float32)
        np.testing.assert_array_equal(out["x"][part].ravel(), full)
    client.finalize()


def test_chain_reset_on_resize_grow_and_shrink(cluster):
    client = _delta_client(cluster)
    data = np.arange(512, dtype=np.float32)
    client.add_adapt("x", data.shape, "float32", num_parts=4)
    client.commit(0, {"x": _parts(data, 4)}, blocking=True, drain=False)
    h = client.commit(1, {"x": _parts(data, 4)}, blocking=True, drain=False)
    assert h.meta.regions["x"].frame == "delta"

    client.commit_redistribution("x", 8)            # grow
    assert E.DELTA_CHAIN_RESET in _events(cluster)
    h = client.commit(2, {"x": _parts(data, 8)}, blocking=True, drain=False)
    assert h.meta.regions["x"].frame == "key"
    h = client.commit(3, {"x": _parts(data, 8)}, blocking=True, drain=False)
    assert h.meta.regions["x"].frame == "delta"

    n_resets = _events(cluster).count(E.DELTA_CHAIN_RESET)
    client.commit_redistribution("x", 2)            # shrink
    assert _events(cluster).count(E.DELTA_CHAIN_RESET) == n_resets + 1
    h = client.commit(4, {"x": _parts(data, 2)}, blocking=True, drain=False)
    assert h.meta.regions["x"].frame == "key"
    client.finalize()


def test_chain_reset_on_rank_failure(cluster):
    client = _delta_client(cluster)
    data = np.arange(512, dtype=np.float32)
    client.add_adapt("x", data.shape, "float32", num_parts=4)
    client.commit(0, {"x": _parts(data, 4)}, blocking=True, drain=False)
    h = client.commit(1, {"x": _parts(data, 4)}, blocking=True, drain=False)
    assert h.meta.regions["x"].frame == "delta"
    cluster.controller.bus.publish(E.APP_RANK_FAILED, app="app", rank=0)
    assert E.DELTA_CHAIN_RESET in _events(cluster)
    h = client.commit(2, {"x": _parts(data, 4)}, blocking=True, drain=False)
    assert h.meta.regions["x"].frame == "key"
    client.finalize()


def test_chain_reset_on_chain_root_demotion(tmp_path):
    """Demoting a chain frame out of L1 resets the chain (the policy keeps
    replay fast and never deltas against slow-tier frames)."""
    c = ICheckCluster(n_icheck_nodes=1, n_spare_nodes=0,
                      node_memory=64 << 20, spill_bytes=64 << 20,
                      pfs_root=str(tmp_path / "pfs"),
                      adaptive_interval=False)
    try:
        client = _delta_client(c, ranks=2)
        data = np.arange(512, dtype=np.float32)
        client.add_adapt("x", data.shape, "float32", num_parts=2)
        client.commit(0, {"x": _parts(data, 2)}, blocking=True, drain=False)
        h = client.commit(1, {"x": _parts(data, 2)}, blocking=True,
                          drain=False)
        assert h.meta.regions["x"].frame == "delta"
        # demote the chain-root shard (ckpt 0) out of L1
        mgr = next(m for m in c.controller.managers()
                   if m.store.has(ShardKey("app", 0, "x", 0)))
        assert mgr.store.demote(ShardKey("app", 0, "x", 0))
        assert E.DELTA_CHAIN_RESET in _events(c)
        h = client.commit(2, {"x": _parts(data, 2)}, blocking=True,
                          drain=False)
        assert h.meta.regions["x"].frame == "key"
        # the demoted frame is still readable: older chains stay restorable
        meta, out, _ = client.restart()
        assert meta.step == 2
        client.finalize()
    finally:
        c.close()


def test_missing_chain_link_skips_to_intact_checkpoint(cluster):
    """Losing a mid-chain frame makes every dependent unrestorable: the
    replay path surfaces a clean RestoreError (never garbage), and
    latest_restartable skips the broken candidates to the intact keyframe.
    """
    client = _delta_client(cluster)
    data = np.arange(2048, dtype=np.float32)
    client.add_adapt("x", data.shape, "float32", num_parts=2)
    for step in range(3):
        data[step] += 1.0
        h = client.commit(step, {"x": _parts(data, 2)}, blocking=True,
                          drain=False)
    assert h.meta.regions["x"].chain == (0, 1, 2)
    # lose the middle delta frame from every tier
    for mgr in cluster.controller.managers():
        mgr.store.drop_checkpoint("app", 1)
    broken = cluster.controller.app("app").checkpoints[2]
    with pytest.raises(RestoreError):
        client._fetch_decoded(broken.regions["x"], 2, 0)
    res = client.restart()
    assert res is not None
    meta, out, _ = res
    assert meta.ckpt_id == 0                    # the self-contained keyframe
    client.finalize()


def test_corrupt_chain_link_raises_restore_error(cluster):
    client = _delta_client(cluster)
    data = np.arange(2048, dtype=np.float32)
    client.add_adapt("x", data.shape, "float32", num_parts=2)
    frames = []
    for step in range(2):
        data[step] += 1.0                   # low churn: keep the delta sparse
        h = client.commit(step, {"x": _parts(data, 2)}, blocking=True,
                          drain=False)
        frames.append(h.meta.regions["x"].frame)
    assert frames == ["key", "delta"]
    # overwrite the keyframe's stored bytes with garbage (valid crc, so the
    # tier serves it — the codec must still refuse to decode it)
    key = ShardKey("app", 0, "x", 0)
    for mgr in cluster.controller.managers():
        if mgr.store.has(key):
            mgr.store.put(key, b"\x7fgarbage-frame" * 3)
    with pytest.raises(RestoreError):
        client.restart()
    client.finalize()


def test_plain_q8_feeds_codec_gauges(cluster):
    """codec='q8' commits must feed the compression-ratio gauge too (an
    operator comparing q8 vs q8-delta must not see q8 as a no-op)."""
    client = ICheckClient("app", cluster.controller, ranks=2,
                          codec="q8").init()
    data = np.random.default_rng(8).standard_normal(1 << 14) \
        .astype(np.float32)
    client.add_adapt("x", data.shape, "float32", num_parts=2)
    client.commit(0, {"x": _parts(data, 2)}, blocking=True, drain=False)
    tel = cluster.telemetry.snapshot()["per_app"]["app"]
    assert tel["codec_raw_bytes"] == data.nbytes
    assert 3.5 < tel["codec_compression_ratio"] < 4.5
    client.finalize()


def test_device_q8_snapshot_feeds_codec_gauges(cluster):
    """The device-encoded commit_snapshot path publishes codec telemetry
    for plain q8 too, not just q8-delta."""
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from repro.core import snapshot_pytree

    client = ICheckClient("app", cluster.controller, ranks=1,
                          codec="q8").init()
    data = np.random.default_rng(9).standard_normal(1 << 14) \
        .astype(np.float32)
    snap = snapshot_pytree({"w": jnp.asarray(data)}, step=0, codec="q8")
    client.commit_snapshot(snap, blocking=True, drain=False)
    tel = cluster.telemetry.snapshot()["per_app"]["app"]
    assert tel["codec_raw_bytes"] == data.nbytes
    assert 3.5 < tel["codec_compression_ratio"] < 4.5
    client.finalize()


def test_failed_ancestor_cascades_to_chain_dependents(cluster):
    """A failed chain frame makes every non-durable dependent delta
    checkpoint unrestorable — latest_restartable must skip them and fall
    back to the intact keyframe instead of raising mid-replay."""
    client = _delta_client(cluster)
    data = np.arange(2048, dtype=np.float32)
    client.add_adapt("x", data.shape, "float32", num_parts=2)
    for step in range(3):                       # key, delta, delta
        data[step] += 1.0
        client.commit(step, {"x": _parts(data, 2)}, blocking=True,
                      drain=False)
    cluster.controller.catalog.mark_failed("app", 1)
    ev = _events(cluster)
    assert ev.count(E.CKPT_FAILED) == 2         # ckpt 1 and its dependent 2
    meta, out, _ = client.restart()
    assert meta.ckpt_id == 0                    # fell back to the keyframe
    client.finalize()


def test_retention_protects_chain_ancestors(tmp_path):
    """keep_l3 retention must not expire a keyframe that surviving delta
    checkpoints still replay through."""
    with ICheckCluster(n_icheck_nodes=2, n_spare_nodes=0,
                       node_memory=64 << 20, pfs_root=str(tmp_path / "pfs"),
                       l3_root=str(tmp_path / "l3"), keep_l3=2,
                       adaptive_interval=False) as c:
        client = _delta_client(c, ranks=2)
        data = np.arange(4096, dtype=np.float32)
        client.add_adapt("x", data.shape, "float32", num_parts=2)
        for step in range(4):                   # key + 3 deltas, chain (0..3)
            data[step] += 1.0
            h = client.commit(step, {"x": _parts(data, 2)}, blocking=True)
            c.controller.wait_for_drains(timeout=30)
            c.controller.wait_for_uploads(timeout=30)
        assert h.meta.regions["x"].chain == (0, 1, 2, 3)
        # keep_l3=2 would retain only ckpts 2,3 — but 0 (the keyframe) and
        # 1 are chain ancestors of the survivors and must be protected
        assert c.l3.has_shard(ShardKey("app", 0, "x", 0))
        meta, out, _ = client.restart()
        assert meta.ckpt_id == 3
        got = np.concatenate([out["x"][i] for i in range(2)])
        err = np.abs(got - data).max()
        assert err <= np.abs(data).max() / 127 * 0.51
        client.finalize()


# ====================================== dtype sweep through a full restart
@pytest.mark.parametrize("dtype", FLOAT_DTYPES)
def test_dtype_sweep_commit_restart_cold_l3(tmp_path, dtype):
    """f32/bf16/f16 regions survive commit → drain → L3 trickle → loss of
    L1+PFS → cold L3 manifest scan, with the dtype recorded in the manifest
    and honored on restore."""
    pfs_root = str(tmp_path / "pfs")
    l3_root = str(tmp_path / "l3")
    rng = np.random.default_rng(7)
    data = rng.standard_normal(4096).astype(np.dtype(dtype))
    with ICheckCluster(n_icheck_nodes=2, n_spare_nodes=0,
                       node_memory=64 << 20, pfs_root=pfs_root,
                       l3_root=l3_root, adaptive_interval=False) as c:
        client = _delta_client(c, ranks=2)
        client.add_adapt("x", data.shape, dtype, num_parts=2)
        client.commit(0, {"x": _parts(data, 2)}, blocking=True)
        h = client.commit(1, {"x": _parts(data, 2)}, blocking=True)
        assert h.meta.regions["x"].frame == "delta"
        c.controller.wait_for_drains(timeout=30)
        c.controller.wait_for_uploads(timeout=30)
        manifest = c.pfs.read_manifest("app", 1)
        assert manifest.regions["x"].dtype == dtype
        assert manifest.regions["x"].codec == "q8-delta"
        assert manifest.regions["x"].chain == (0, 1)
        client.finalize()
    import shutil
    shutil.rmtree(pfs_root)
    with ICheckCluster(n_icheck_nodes=2, n_spare_nodes=0,
                       node_memory=64 << 20, pfs_root=pfs_root,
                       l3_root=l3_root, adaptive_interval=False) as c2:
        client = ICheckClient("app", c2.controller, ranks=2,
                              codec="q8-delta").init()
        meta, parts, level = client.restart()
        assert level == "l3"
        got = np.concatenate([parts["x"][i] for i in range(2)])
        assert got.dtype == np.dtype(dtype)
        err = np.abs(_f32(got) - _f32(data)).max()
        assert err <= np.abs(_f32(data)).max() / 127 * 0.51 + 0.01
        client.finalize()


# ==================================== device-side encode + commit_snapshot
def test_device_snapshot_delta_commit_and_restart(cluster):
    jax = pytest.importorskip("jax")
    jnp = jax.numpy
    from repro.core import snapshot_pytree

    client = ICheckClient("app", cluster.controller, ranks=1,
                          codec="q8-delta").init()
    rng = np.random.default_rng(11)
    tree = {"w": jnp.asarray(rng.standard_normal(700).astype(np.float32)),
            "n_steps": jnp.asarray(3, jnp.int32)}
    snap = snapshot_pytree(tree, step=0, codec="q8-delta",
                           chain_lookup=client.delta_chain_lookup)
    enc = snap.regions["w"].encoded
    assert enc is not None and enc.frame == "key" and not snap.regions["w"].parts
    assert snap.regions["n_steps"].encoded is None      # ints travel raw
    client.commit_snapshot(snap, blocking=True, drain=False)

    tree["w"] = tree["w"].at[:4].add(1.0)
    snap2 = snapshot_pytree(tree, step=1, codec="q8-delta",
                            chain_lookup=client.delta_chain_lookup)
    enc2 = snap2.regions["w"].encoded
    assert enc2.frame == "delta" and enc2.parent_chain == (0,)
    assert sum(map(len, enc2.blobs.values())) < \
        sum(map(len, enc.blobs.values())) / 2
    h = client.commit_snapshot(snap2, blocking=True, drain=False)
    assert h.meta.regions["w"].chain == (0, 1)

    meta, out, _ = client.restart()
    assert meta.step == 1
    w = out["w"][0]
    bound = np.abs(np.asarray(tree["w"])).max() / 127 * 0.51
    assert np.abs(w - np.asarray(tree["w"])).max() <= bound
    assert out["n_steps"][0] == 3

    # telemetry saw the incremental commits
    tel = cluster.telemetry.snapshot()["per_app"]["app"]
    assert tel["delta_key_frames"] >= 1 and tel["delta_delta_frames"] >= 1
    assert tel["codec_compression_ratio"] > 3.0
    prom = cluster.telemetry.prometheus()
    assert "icheck_codec_compression_ratio" in prom
    assert "icheck_codec_encode_seconds" in prom
    client.finalize()


def test_delta_save_encodes_with_quantize_alone(cluster, monkeypatch):
    """A q8-delta save against a live chain runs one device kernel,
    ``quantize``: the changed blocks are counted on the host against the
    catalog's previous codes, which stay on the host only."""
    jax = pytest.importorskip("jax")
    jnp = jax.numpy
    from repro.core import snapshot_pytree
    from repro.kernels import ckpt_codec

    def refuse(*a, **k):
        raise AssertionError("the save called quantize_delta")

    monkeypatch.setattr(ckpt_codec, "quantize_delta", refuse)
    client = ICheckClient("app", cluster.controller, ranks=1,
                          codec="q8-delta").init()
    rng = np.random.default_rng(17)
    tree = {"w": jnp.asarray(rng.standard_normal(1000).astype(np.float32))}
    for step in range(2):
        snap = snapshot_pytree(tree, step=step, codec="q8-delta",
                               chain_lookup=client.delta_chain_lookup)
        h = client.commit_snapshot(snap, blocking=True, drain=False)
        tree["w"] = tree["w"].at[:4].add(1.0)
    assert snap.regions["w"].encoded.frame == "delta"
    assert h.meta.regions["w"].frame == "delta"
    assert h.meta.regions["w"].chain == (0, 1)
    states = client.delta_chain_lookup("w", 1).parts
    assert states
    for st in states.values():
        for f in dataclasses.fields(st):
            assert not isinstance(getattr(st, f.name), jax.Array), f.name
    client.finalize()


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_device_snapshot_frames_match_reference_packer(tmp_path, impl):
    """Three device-encoded saves (a first keyframe, a full-churn save the
    changed-block count sends out as keyframes, a low-churn save that keeps
    deltas) ship exactly the reference packer's frames of the same codes,
    restart bit-identical to the chain replay, and note the count on their
    ``snapshot/frame``."""
    jax = pytest.importorskip("jax")
    jnp = jax.numpy
    from repro.core import snapshot_pytree
    from repro.kernels.ckpt_codec import quantize
    from repro.obs import trace_id_for

    rng = np.random.default_rng(13)
    host = {"w": rng.standard_normal((40, 256)).astype(np.float32),
            "b": rng.standard_normal(300).astype(np.float32)}
    c = ICheckCluster(n_icheck_nodes=2, trace=True,
                      obs_dir=str(tmp_path / "obs"))
    client = ICheckClient("app", c.controller, ranks=1,
                          codec="q8-delta").init()
    try:
        chain = {name: None for name in host}
        sent = {name: [] for name in host}
        for step, churn in enumerate(["first", "full", "low"]):
            if churn == "full":                  # every value moves
                host = {k: v * 1.5 + 0.25 for k, v in host.items()}
            elif churn == "low":                 # one block of w moves
                host["w"] = host["w"].copy()
                host["w"][0, :4] += 1.0
            tree = {k: jnp.asarray(v) for k, v in host.items()}
            snap = snapshot_pytree(tree, step=step, codec="q8-delta",
                                   chain_lookup=client.delta_chain_lookup,
                                   impl=impl, tracer=c.tracer)
            client.commit_snapshot(snap, blocking=True, drain=False)
            frames = {s.args["region"]: s.args
                      for s in c.tracer.spans(trace_id_for("app", step))
                      if s.name == "snapshot/frame"}
            for name, x in host.items():
                q, sc = (np.asarray(v) for v in quantize(tree[name],
                                                         impl=impl))
                blobs, chain[name], frame = _ref_pack_q8_region(
                    {0: (x.size, q, sc)}, chain[name])
                enc = snap.regions[name].encoded
                assert enc.frame == frame
                assert enc.blobs == blobs
                sent[name] = [enc.blobs[0]] if frame == "key" \
                    else sent[name] + [enc.blobs[0]]
                args = frames[name]
                assert args["frame"] == frame
                assert args["blocks"] == chain[name][0].codes.shape[0]
                assert args["delta_built"] == (frame == "delta")
                assert not args["delta_discarded"]
                if churn == "first":
                    assert frame == "key" and args["changed_blocks"] is None
                elif churn == "full":            # decided from the count
                    assert frame == "key"
                    assert args["changed_blocks"] == args["blocks"]
                else:
                    assert frame == "delta"
                    assert args["changed_blocks"] == (name == "w")
        meta, out, _ = client.restart()
        assert meta.step == 2
        for name in host:
            assert meta.regions[name].chain == (1, 2)
            want = np.frombuffer(q8_chain_decode(sent[name], "float32"),
                                 np.float32)
            np.testing.assert_array_equal(out[name][0].ravel(), want)
    finally:
        client.finalize()
        c.close()


def test_elastic_trainer_q8_delta_roundtrip():
    """ElasticTrainer(codec='q8-delta') commits via the device-encoded
    snapshot path, survives a resize, and reports codec telemetry."""
    pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.optim import AdamWConfig
    from repro.train import ElasticTrainer

    cfg = get_config("yi-6b", tiny=True)
    shape = ShapeConfig("t", "train", 32, 4)
    with ICheckCluster(n_icheck_nodes=2) as cluster:
        t = ElasticTrainer(cfg, shape, cluster, app_id="app", seed=5,
                           opt_cfg=AdamWConfig(lr=1e-3), commit_every=2,
                           probe_every=0, total_steps=12, codec="q8-delta")
        t.run(4)
        cluster.rm.schedule_resize("app", 2)
        t.run(4)
        assert t.resizes == 1
        tel = cluster.telemetry.snapshot()["per_app"]["app"]
        assert tel["delta_key_frames"] > 0
        assert tel["codec_compression_ratio"] > 3.0
        t.finalize()


def test_stale_device_encode_falls_back_to_keyframe(cluster):
    """A delta snapshot whose chain moved (or reset) between encode and
    commit must not be committed as a wrong delta — the carried codes are
    re-framed as a self-contained keyframe instead."""
    jax = pytest.importorskip("jax")  # noqa: F841
    import jax.numpy as jnp
    from repro.core import snapshot_pytree

    client = ICheckClient("app", cluster.controller, ranks=1,
                          codec="q8-delta").init()
    tree = {"w": jnp.ones((300,), jnp.float32)}
    client.commit_snapshot(snapshot_pytree(
        tree, step=0, codec="q8-delta",
        chain_lookup=client.delta_chain_lookup), blocking=True, drain=False)
    snap = snapshot_pytree(tree, step=1, codec="q8-delta",
                           chain_lookup=client.delta_chain_lookup)
    assert snap.regions["w"].encoded.frame == "delta"
    # the chain moves underneath (another commit of the same region)
    client.commit_snapshot(snapshot_pytree(
        tree, step=1, codec="q8-delta",
        chain_lookup=client.delta_chain_lookup), blocking=True, drain=False)
    h = client.commit_snapshot(snap, blocking=True, drain=False)
    assert h.meta.regions["w"].frame == "key"
    assert h.meta.regions["w"].chain == (h.meta.ckpt_id,)
    meta, out, _ = client.restart()
    np.testing.assert_allclose(out["w"][0], np.ones(300, np.float32),
                               atol=1 / 127)
    client.finalize()
