"""Persistent plan-artifact store: round trips, the registry disk
tier, poisoned-artifact robustness, concurrency, manifest prewarm and
the CLI.

The safety contract under test (docs/artifact_cache.md): a warm load
is bit-exact with a cold build and counts ZERO builds; a poisoned
artifact (corrupt bytes, version mismatch, stale index digest,
truncated payload, racing writers) NEVER loads — the typed reason is
counted (``spfft_store_rejects_total{reason}``) and the caller falls
back to a clean rebuild.
"""

import dataclasses
import hashlib
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from spfft_tpu import obs
from spfft_tpu.errors import PlanArtifactError
from spfft_tpu.plan import TransformPlan, restore_plan
from spfft_tpu.indexing import build_index_plan
from spfft_tpu.serve.registry import PlanRegistry
from spfft_tpu.serve import store as store_mod
from spfft_tpu.serve.store import (MAGIC, PLAN_MANIFEST_ENV,
                                   PlanArtifactStore, load_manifest,
                                   parse_artifact, serialize_artifact,
                                   signature_key)
from spfft_tpu.types import Scaling, TransformType
from spfft_tpu.utils.workloads import (sort_triplets_stick_major,
                                       spherical_cutoff_triplets)

DIM = 20


def _triplets(dim=DIM, r2c=False):
    tr = sort_triplets_stick_major(spherical_cutoff_triplets(dim),
                                   (dim, dim, dim))
    if r2c:
        tr = tr[tr[:, 0] >= 0]
    return tr


def _values(plan, seed=7):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(
        (plan.index_plan.num_values, 2)).astype(np.float32)


def _build_store(tmp_path, dim=DIM, **kwargs):
    store = PlanArtifactStore(str(tmp_path / "store"))
    reg = PlanRegistry(store=store)
    tr = _triplets(dim)
    sig, plan = reg.get_or_build(TransformType.C2C, dim, dim, dim, tr,
                                 **kwargs)
    store.drain()
    return store, reg, tr, sig, plan


def _rewrite(path, header, payload):
    """Re-assemble an artifact file from (possibly tampered) parts,
    keeping the length/checksum fields consistent with ``payload``."""
    header = dict(header)
    header["payload_sha256"] = hashlib.sha256(payload).hexdigest()
    header["payload_len"] = len(payload)
    hbytes = json.dumps(header, sort_keys=True).encode()
    with open(path, "wb") as f:
        f.write(b"".join([MAGIC, b"%016x\n" % len(hbytes), hbytes,
                          payload]))


def _split_artifact(path):
    data = open(path, "rb").read()
    off = len(MAGIC)
    hlen = int(data[off:off + 16], 16)
    off += 17
    return json.loads(data[off:off + hlen]), data[off + hlen:]


# -- round trips -------------------------------------------------------------
def test_artifact_roundtrip_bit_exact(tmp_path):
    store, reg, tr, sig, plan = _build_store(tmp_path)
    vals = _values(plan)
    want_b = np.asarray(plan.backward(vals))
    want_f = np.asarray(plan.forward(want_b, scaling=Scaling.FULL))

    got = PlanArtifactStore(store.root).load_signature(sig)
    assert got is not None
    sig2, plan2 = got
    assert sig2 == sig
    assert plan2._build_thread is None  # no background build ever ran
    assert np.array_equal(np.asarray(plan2.backward(vals)), want_b)
    assert np.array_equal(
        np.asarray(plan2.forward(want_b, scaling=Scaling.FULL)), want_f)


def test_warm_registry_resolves_with_zero_builds(tmp_path):
    store, reg, tr, sig, plan = _build_store(tmp_path)
    assert reg.stats()["builds"] == 1
    assert reg.stats()["store_spills"] == 1
    vals = _values(plan)
    want = np.asarray(plan.backward(vals))

    before = {
        kind: obs.GLOBAL_COUNTERS.get("spfft_compile_events_total",
                                      kind=kind)
        for kind in ("registry_build", "compression_tables")}
    reg2 = PlanRegistry(store=PlanArtifactStore(store.root))
    sig2, plan2 = reg2.get_or_build(TransformType.C2C, DIM, DIM, DIM,
                                    tr)
    stats = reg2.stats()
    assert sig2 == sig
    assert stats["builds"] == 0
    assert stats["store_hits"] == 1
    # no index-table build, no background table-build span
    for kind, was in before.items():
        assert obs.GLOBAL_COUNTERS.get("spfft_compile_events_total",
                                       kind=kind) == was
    assert np.array_equal(np.asarray(plan2.backward(vals)), want)


def test_wrapped_spelling_resolves_via_signature_tier(tmp_path):
    """A request spelled with wrapped (non-negative) indices misses the
    raw-bytes alias but lands on the SAME canonical signature — the
    registry's signature read-through then loads the artifact instead
    of constructing a plan."""
    store, reg, tr, sig, plan = _build_store(tmp_path)
    wrapped = np.where(tr < 0, tr + DIM, tr).astype(tr.dtype)
    assert not np.array_equal(wrapped, tr)
    reg2 = PlanRegistry(store=PlanArtifactStore(store.root))
    sig2, plan2 = reg2.get_or_build(TransformType.C2C, DIM, DIM, DIM,
                                    wrapped)
    assert sig2 == sig
    assert reg2.stats()["builds"] == 0
    assert reg2.stats()["store_hits"] == 1


def test_pallas_tables_roundtrip(tmp_path):
    """use_pallas=True builds the kernel tables on CPU; the artifact
    must carry them and the restored plan must reuse them (and stay
    bit-exact) without any cover build."""
    store, reg, tr, sig, plan = _build_store(tmp_path, use_pallas=True)
    assert plan._pallas_box is not None
    vals = _values(plan)
    want = np.asarray(plan.backward(vals))
    got = PlanArtifactStore(store.root).load_signature(
        sig, plan_kwargs={"use_pallas": True})
    assert got is not None
    _, plan2 = got
    assert plan2._pallas_box is not None
    assert plan2._pallas_box["dec"] is not None
    assert plan2._build_thread is None
    assert np.array_equal(np.asarray(plan2.backward(vals)), want)


def test_use_pallas_demand_without_tables_rebuilds(tmp_path):
    """An artifact spilled without kernel tables cannot honour
    use_pallas=True — the load declines (typed 'incompatible') and the
    registry rebuilds with the tables."""
    store, reg, tr, sig, plan = _build_store(tmp_path,
                                             use_pallas=False)
    reg2 = PlanRegistry(store=PlanArtifactStore(store.root))
    sig2, plan2 = reg2.get_or_build(TransformType.C2C, DIM, DIM, DIM,
                                    tr, use_pallas=True)
    assert reg2.stats()["builds"] == 1
    assert plan2._pallas is not None   # property joins the fresh build
    # both load attempts (raw alias, then signature tier) declined
    assert reg2.store.stats()["rejects"].get("incompatible", 0) >= 1


def test_aot_executables_install_and_disable(tmp_path, monkeypatch):
    store, reg, tr, sig, plan = _build_store(tmp_path)
    got = PlanArtifactStore(store.root).load_signature(sig)
    assert got is not None
    _, plan2 = got
    # this container's jax has jax.export for the CPU platform
    assert plan2._aot is not None
    assert set(plan2._aot) == {"backward", "forward_none",
                               "forward_full", "batched_backward",
                               "batched_forward_none",
                               "batched_forward_full", "pair_none",
                               "pair_full"}
    # disabled: the spilled artifact carries no AOT blobs at all
    monkeypatch.setenv("SPFFT_TPU_PLAN_STORE_AOT", "0")
    store2 = PlanArtifactStore(str(tmp_path / "store2"))
    store2.save_plan(sig, plan, triplets=tr)
    got2 = store2.load_signature(sig)
    assert got2 is not None
    assert got2[1]._aot is None


def test_aot_call_failure_falls_back_to_jit(tmp_path):
    """An AOT executable that disagrees with this process's table
    pytree must never fail a request: the call falls back to the jit
    path permanently (counted, bit-exact)."""
    store, reg, tr, sig, plan = _build_store(tmp_path)
    got = PlanArtifactStore(store.root).load_signature(sig)
    _, plan2 = got

    class Broken:
        def call(self, *a, **k):
            raise RuntimeError("pytree mismatch")

    plan2._aot["backward"] = Broken()
    vals = _values(plan)
    want = np.asarray(plan.backward(vals))
    before = obs.GLOBAL_COUNTERS.get("spfft_store_aot_skipped_total",
                                     reason="call_failed")
    assert np.array_equal(np.asarray(plan2.backward(vals)), want)
    assert "backward" not in plan2._aot   # dropped permanently
    assert obs.GLOBAL_COUNTERS.get("spfft_store_aot_skipped_total",
                                   reason="call_failed") == before + 1
    # later calls go straight through the jit path
    assert np.array_equal(np.asarray(plan2.backward(vals)), want)


def test_aot_batched_and_pair_roundtrip_bit_exact(tmp_path):
    """The batched (symbolic leading batch dim) and identity fused-pair
    executables round-trip through the store and serve requests
    bit-exactly against a fresh-jit plan — at MULTIPLE batch sizes, so
    one exported module demonstrably covers every B."""
    store, reg, tr, sig, plan = _build_store(tmp_path)
    got = PlanArtifactStore(store.root).load_signature(sig)
    assert got is not None
    _, plan2 = got
    for key in ("batched_backward", "batched_forward_none",
                "batched_forward_full", "pair_none", "pair_full"):
        assert key in plan2._aot, key

    rng = np.random.default_rng(11)
    for b in (1, 3):
        vals_b = rng.standard_normal(
            (b, plan.index_plan.num_values, 2)).astype(np.float32)
        want_b = np.asarray(plan.backward_batched(vals_b))
        assert np.array_equal(
            np.asarray(plan2.backward_batched(vals_b)), want_b)
        for scaling in (Scaling.NONE, Scaling.FULL):
            assert np.array_equal(
                np.asarray(plan2.forward_batched(want_b,
                                                 scaling=scaling)),
                np.asarray(plan.forward_batched(want_b,
                                                scaling=scaling)))
    # the AOT entries survived every dispatch (no silent call_failed
    # fallback ate them)
    for key in ("batched_backward", "batched_forward_none",
                "batched_forward_full"):
        assert key in plan2._aot, key

    vals = _values(plan, seed=12)
    for scaling in (Scaling.NONE, Scaling.FULL):
        assert np.array_equal(
            np.asarray(plan2.apply_pointwise(vals, scaling=scaling)),
            np.asarray(plan.apply_pointwise(vals, scaling=scaling)))
    assert "pair_none" in plan2._aot and "pair_full" in plan2._aot


# -- poisoned artifacts ------------------------------------------------------
def _reject_count(store, reason):
    return store.stats()["rejects"].get(reason, 0)


def test_corrupt_artifact_never_loads_and_rebuilds(tmp_path):
    store, reg, tr, sig, plan = _build_store(tmp_path)
    path = store.artifact_path(signature_key(sig))
    data = open(path, "rb").read()
    with open(path, "wb") as f:  # flip bytes inside the payload
        f.write(data[:-64] + b"\x00" * 64)
    reg2 = PlanRegistry(store=PlanArtifactStore(store.root))
    sig2, plan2 = reg2.get_or_build(TransformType.C2C, DIM, DIM, DIM,
                                    tr)
    assert sig2 == sig                      # clean rebuild, same plan
    assert reg2.stats()["builds"] == 1
    assert _reject_count(reg2.store, "corrupt") >= 1
    vals = _values(plan)
    assert np.array_equal(np.asarray(plan2.backward(vals)),
                          np.asarray(plan.backward(vals)))


def test_truncated_artifact_rejected(tmp_path):
    store, reg, tr, sig, plan = _build_store(tmp_path)
    path = store.artifact_path(signature_key(sig))
    data = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(data[: len(data) // 2])
    assert PlanArtifactStore(store.root).load_signature(sig) is None


def test_garbage_file_rejected(tmp_path):
    store, reg, tr, sig, plan = _build_store(tmp_path)
    path = store.artifact_path(signature_key(sig))
    with open(path, "wb") as f:
        f.write(b"not an artifact at all")
    s2 = PlanArtifactStore(store.root)
    assert s2.load_signature(sig) is None
    assert _reject_count(s2, "corrupt") == 1


def test_version_header_mismatch_rejected(tmp_path):
    store, reg, tr, sig, plan = _build_store(tmp_path)
    path = store.artifact_path(signature_key(sig))
    header, payload = _split_artifact(path)
    header["format_version"] = 999
    _rewrite(path, header, payload)
    s2 = PlanArtifactStore(store.root)
    assert s2.load_signature(sig) is None
    assert _reject_count(s2, "version_mismatch") == 1


def test_table_schema_mismatch_rejected(tmp_path):
    store, reg, tr, sig, plan = _build_store(tmp_path)
    path = store.artifact_path(signature_key(sig))
    header, payload = _split_artifact(path)
    header["table_schema"] = 0
    _rewrite(path, header, payload)
    s2 = PlanArtifactStore(store.root)
    assert s2.load_signature(sig) is None
    assert _reject_count(s2, "version_mismatch") == 1


def test_table_schema_of_narrow_fused_layout_rejected(tmp_path):
    """An artifact written before the fused tables carried the
    super-tile geometry (table schema 2) is refused as a version
    mismatch and rebuilt, never loaded."""
    store, reg, tr, sig, plan = _build_store(tmp_path)
    path = store.artifact_path(signature_key(sig))
    header, payload = _split_artifact(path)
    header["table_schema"] = 2
    _rewrite(path, header, payload)
    s2 = PlanArtifactStore(store.root)
    assert s2.load_signature(sig) is None
    assert _reject_count(s2, "version_mismatch") == 1


def test_fused_super_tile_tables_roundtrip(tmp_path, monkeypatch):
    """The fused kernels' super-tile tables survive the artifact: the
    restored plan runs the same geometry from the stored tables and
    stays bit-exact."""
    monkeypatch.setenv("SPFFT_TPU_FORCE_MATMUL_DFT", "1")
    monkeypatch.setenv("SPFFT_TPU_FUSED_INTERPRET", "1")
    nz = 128
    tr = np.asarray([(x, y, z) for x in range(4) for y in range(4)
                     for z in range(0, nz, 2)], np.int32)
    store = PlanArtifactStore(str(tmp_path / "store"))
    sig, plan = PlanRegistry(store=store).get_or_build(
        TransformType.C2C, 4, 4, nz, tr, use_pallas=True)
    store.drain()
    assert plan.fused_geometry == {"backward": "super_tile",
                                   "forward": "super_tile"}
    vals = _values(plan)
    want = np.asarray(plan.backward(vals))
    got = PlanArtifactStore(store.root).load_signature(
        sig, plan_kwargs={"use_pallas": True})
    assert got is not None
    _, plan2 = got
    assert plan2._build_thread is None
    assert plan2.fused_geometry == plan.fused_geometry
    for which in ("dec", "cmp"):
        a, b = plan._fused[which], plan2._fused[which]
        np.testing.assert_array_equal(a.toff, b.toff)
        assert a.kp_rows == b.kp_rows and a.p_tiles == b.p_tiles
    assert np.array_equal(np.asarray(plan2.backward(vals)), want)


def test_stale_index_digest_rejected(tmp_path):
    """A payload whose checksum is VALID but whose tables no longer
    digest to the signature they claim (the hand-edited/stale-artifact
    case) must reject as digest_mismatch, never load."""
    import io
    store, reg, tr, sig, plan = _build_store(tmp_path)
    path = store.artifact_path(signature_key(sig))
    header, payload = _split_artifact(path)
    with np.load(io.BytesIO(payload)) as z:
        arrays = {k: z[k] for k in z.files}
    arrays["stick_keys"] = arrays["stick_keys"].copy()
    arrays["stick_keys"][0] += 1       # different sparse set
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    _rewrite(path, header, buf.getvalue())   # checksum recomputed OK
    s2 = PlanArtifactStore(store.root)
    assert s2.load_signature(sig) is None
    assert _reject_count(s2, "digest_mismatch") == 1


def test_parse_artifact_reports_typed_reasons():
    from spfft_tpu.serve.store import StoreReject
    with pytest.raises(StoreReject) as exc:
        parse_artifact(b"garbage")
    assert exc.value.reason == "corrupt"


def test_concurrent_writer_race_stays_loadable(tmp_path):
    """Many threads spilling the same artifact concurrently (the
    multi-process analogue runs through the same atomic os.replace):
    whatever interleaving wins, the surviving file parses and loads."""
    store, reg, tr, sig, plan = _build_store(tmp_path)
    errs = []

    def spill():
        try:
            store.save_plan(sig, plan, triplets=tr)
        except Exception as exc:  # pragma: no cover
            errs.append(exc)

    threads = [threading.Thread(target=spill) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    with open(store.artifact_path(signature_key(sig)), "rb") as f:
        parse_artifact(f.read())  # must not raise
    assert PlanArtifactStore(store.root).load_signature(sig) is not None
    leftovers = [n for n in os.listdir(store._dir("artifacts"))
                 if n.startswith(".tmp-")]
    assert not leftovers


# -- registry fuzz with the disk tier ----------------------------------------
def test_registry_fuzz_with_disk_tier(tmp_path):
    """8 threads hammering a store-backed registry across two shapes:
    every result bit-exact vs a serial oracle, one build per shape
    (singleflight holds with the disk tier in the path), and a fresh
    registry over the same store then resolves both with zero builds."""
    store = PlanArtifactStore(str(tmp_path / "store"))
    reg = PlanRegistry(store=store)
    shapes = {16: _triplets(16), 20: _triplets(20)}
    oracles = {}
    for dim, tr in shapes.items():
        ip = build_index_plan(TransformType.C2C, dim, dim, dim, tr)
        p = TransformPlan(ip)
        vals = np.random.default_rng(dim).standard_normal(
            (ip.num_values, 2)).astype(np.float32)
        oracles[dim] = (vals, np.asarray(p.backward(vals)))

    results, errors = [], []

    def worker(tid):
        try:
            for i in range(6):
                dim = 16 if (tid + i) % 2 == 0 else 20
                tr = shapes[dim]
                sig, plan = reg.get_or_build(TransformType.C2C, dim,
                                             dim, dim, tr)
                vals, want = oracles[dim]
                got = np.asarray(plan.backward(vals))
                results.append(np.array_equal(got, want))
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert all(results)
    assert reg.stats()["builds"] == 2
    store.drain()

    reg2 = PlanRegistry(store=PlanArtifactStore(store.root))
    for dim, tr in shapes.items():
        sig, plan = reg2.get_or_build(TransformType.C2C, dim, dim, dim,
                                      tr)
        vals, want = oracles[dim]
        assert np.array_equal(np.asarray(plan.backward(vals)), want)
    assert reg2.stats()["builds"] == 0
    assert reg2.stats()["store_hits"] == 2


# -- gc / manifest / prewarm -------------------------------------------------
def test_gc_evicts_oldest_and_sweeps_aliases(tmp_path):
    store = PlanArtifactStore(str(tmp_path / "store"), max_bytes=0)
    reg = PlanRegistry(store=store)
    for dim in (16, 20):
        reg.get_or_build(TransformType.C2C, dim, dim, dim,
                         _triplets(dim))
    store.drain()
    files = store._artifact_files()
    assert len(files) == 2
    os.utime(files[0][0], (1, 1))  # make one clearly oldest
    keep_bytes = os.path.getsize(files[1][0])
    removed = store.gc(max_bytes=keep_bytes)
    assert len(removed) == 1
    assert len(store._artifact_files()) == 1
    # the surviving artifact's alias still resolves; the evicted one's
    # alias was swept
    live = {os.path.basename(p)[:-5] for p, _, _ in
            store._artifact_files()}
    for name in os.listdir(store._dir("requests")):
        with open(os.path.join(store._dir("requests"), name)) as f:
            assert json.load(f)["artifact"] in live


def test_manifest_warmup_and_strict_failure(tmp_path):
    store, reg, tr, sig, plan = _build_store(tmp_path)
    mpath = str(tmp_path / "manifest.json")
    m = store.write_manifest(mpath)
    assert len(m["entries"]) == 1

    reg2 = PlanRegistry(store=PlanArtifactStore(store.root))
    sigs = reg2.warmup_manifest(mpath, compile=True)
    assert sigs == [sig]
    assert reg2.stats()["builds"] == 0
    assert reg2.get(sig) is not None

    # a poisoned artifact fails strict prewarm loudly ...
    path = store.artifact_path(signature_key(sig))
    open(path, "wb").write(b"junk")
    reg3 = PlanRegistry(store=PlanArtifactStore(store.root))
    with pytest.raises(PlanArtifactError):
        reg3.warmup_manifest(mpath)
    # ... and is skipped (reason counted) when strict=False
    reg4 = PlanRegistry(store=PlanArtifactStore(store.root))
    assert reg4.warmup_manifest(mpath, strict=False) == []
    assert _reject_count(reg4.store, "corrupt") == 1


def test_live_manifest_auto_refresh_on_spill(tmp_path, monkeypatch):
    """With ``SPFFT_TPU_PLAN_MANIFEST`` set, every spill merges its
    entry into the live manifest — deduped on the artifact key — and a
    replacement registry prewarms from it with zero builds."""
    mpath = str(tmp_path / "live-manifest.json")
    monkeypatch.setenv(PLAN_MANIFEST_ENV, mpath)
    before = obs.GLOBAL_COUNTERS.get(
        "spfft_store_manifest_refreshes_total")
    store, reg, tr, sig, plan = _build_store(tmp_path)

    m = load_manifest(mpath)
    assert [e["artifact"] for e in m["entries"]] \
        == [signature_key(sig)]
    assert m["entries"][0]["signature"] == dataclasses.asdict(sig)
    assert m["entries"][0]["num_values"] == plan.index_plan.num_values

    # re-spilling the same plan replaces, never duplicates
    store.save_plan(sig, plan, tr)
    assert len(load_manifest(mpath)["entries"]) == 1

    # a second signature appends
    dim2 = 16
    sig2, _ = reg.get_or_build(TransformType.C2C, dim2, dim2, dim2,
                               _triplets(dim2))
    store.drain()
    m = load_manifest(mpath)
    assert {e["artifact"] for e in m["entries"]} \
        == {signature_key(sig), signature_key(sig2)}
    assert obs.GLOBAL_COUNTERS.get(
        "spfft_store_manifest_refreshes_total") >= before + 3

    reg2 = PlanRegistry(store=PlanArtifactStore(store.root))
    assert set(reg2.warmup_manifest(mpath)) == {sig, sig2}
    assert reg2.stats()["builds"] == 0


def test_live_manifest_concurrent_appends_atomic(tmp_path):
    """16 threads hammering ``append_manifest_entry`` (with key
    collisions) leave one valid, complete, duplicate-free manifest and
    no temp droppings — the read/merge/replace cycle is atomic."""
    store = PlanArtifactStore(str(tmp_path / "store"))
    mpath = str(tmp_path / "live-manifest.json")
    keys = [f"art-{i % 12:02d}" for i in range(16)]  # 12 distinct

    def append(key):
        store.append_manifest_entry(mpath, {
            "artifact": key, "signature": {"k": key}, "bytes": 1})

    threads = [threading.Thread(target=append, args=(k,))
               for k in keys]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    m = load_manifest(mpath)
    got = [e["artifact"] for e in m["entries"]]
    assert sorted(got) == sorted(set(keys))  # all present, none twice
    assert not [n for n in os.listdir(str(tmp_path))
                if n.startswith(".tmp-")]


def test_live_manifest_invalid_file_never_clobbered(tmp_path,
                                                    monkeypatch):
    """An existing-but-invalid manifest is an error for the direct
    append, a counted non-fatal reject for the spill path — and its
    bytes survive untouched either way."""
    from spfft_tpu.errors import InvalidParameterError
    mpath = str(tmp_path / "live-manifest.json")
    open(mpath, "w").write("not a manifest")
    monkeypatch.setenv(PLAN_MANIFEST_ENV, mpath)

    store, reg, tr, sig, plan = _build_store(tmp_path)
    with pytest.raises(InvalidParameterError):
        store.append_manifest_entry(mpath, {"artifact": "x"})
    # the spill itself (hook included) already ran and must not have
    # failed: the artifact landed, the manifest stayed as-is
    assert store.load_signature(sig) is not None
    assert open(mpath).read() == "not a manifest"
    assert _reject_count(store, "io") >= 1


def test_executor_boot_prewarm_from_manifest_env(tmp_path, monkeypatch):
    from spfft_tpu.serve import ServeExecutor
    store, reg, tr, sig, plan = _build_store(tmp_path)
    mpath = str(tmp_path / "manifest.json")
    store.write_manifest(mpath)
    monkeypatch.setenv("SPFFT_TPU_PLAN_MANIFEST", mpath)
    reg2 = PlanRegistry(store=PlanArtifactStore(store.root))
    with ServeExecutor(reg2, batching=False) as ex:
        assert reg2.stats()["builds"] == 0
        assert reg2.get(sig) is not None   # warm before traffic
        vals = _values(plan)
        fut = ex.submit(sig, vals)
        got = np.asarray(fut.result(timeout=60))
    assert np.array_equal(got, np.asarray(plan.backward(vals)))


# -- default-store resolution ------------------------------------------------
def test_env_var_attaches_default_store(tmp_path, monkeypatch):
    import spfft_tpu.serve.store as sm
    monkeypatch.setenv("SPFFT_TPU_PLAN_STORE",
                       str(tmp_path / "envstore"))
    monkeypatch.setattr(sm, "_DEFAULT_STORES", {})
    reg = PlanRegistry()
    assert reg.store is not None
    assert reg.store.root == str(tmp_path / "envstore")
    # store=False forces the tier off regardless of the env
    assert PlanRegistry(store=False).store is None


def test_config_path_setting_roundtrip(tmp_path):
    from spfft_tpu.control.config import ServeConfig
    cfg = ServeConfig()
    assert cfg.plan_store_path == ""
    cfg.set_path("plan_store_path", str(tmp_path / "s"))
    art = str(tmp_path / "cfg.json")
    cfg.save(art)
    cfg2 = ServeConfig.load(art)
    assert cfg2.plan_store_path == str(tmp_path / "s")
    assert cfg2.get("plan_store_max_bytes") \
        == ServeConfig.default("plan_store_max_bytes")


# -- CLI ---------------------------------------------------------------------
def test_cli_seed_manifest_prewarm_verify_gc(tmp_path, capsys):
    root = str(tmp_path / "cli")
    assert store_mod.main(["seed", root, "--dim", "16",
                           "--reference", "--json"]) == 0
    seed = json.loads(capsys.readouterr().out)
    assert seed["builds"] == 1 and seed["store"]["spills"] == 1

    assert store_mod.main(["manifest", root]) == 0
    capsys.readouterr()
    assert store_mod.main(["prewarm", root, "--compile",
                           "--check-reference", "--strict",
                           "--json"]) == 0
    warm = json.loads(capsys.readouterr().out)
    assert warm["ok"] and warm["builds"] == 0
    assert warm["reference_bit_exact"] is True
    assert warm["compile_events"]["registry_build"] == 0
    assert warm["compile_events"]["compression_tables"] == 0

    assert store_mod.main(["verify", root, "--json"]) == 0
    rows = json.loads(capsys.readouterr().out)["rows"]
    assert rows and all(r["ok"] for r in rows)

    # poison it: verify and strict prewarm both go red
    store = PlanArtifactStore(root)
    key = rows[0]["key"]
    open(store.artifact_path(key), "wb").write(b"junk")
    assert store_mod.main(["verify", root, "--json"]) == 1
    capsys.readouterr()
    assert store_mod.main(["prewarm", root, "--strict", "--json"]) == 1
    capsys.readouterr()

    assert store_mod.main(["gc", root, "--max-bytes", "1"]) == 0


def test_store_smoke_cross_process(tmp_path):
    """The make store-smoke contract, as a test: process A builds and
    spills, process B (a genuinely fresh interpreter) warm-loads with
    builds==0, no table-build spans, and bit-exact outputs against the
    recorded reference."""
    root = str(tmp_path / "xproc")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    a = subprocess.run(
        [sys.executable, "-m", "spfft_tpu.serve.store", "seed", root,
         "--dim", "16", "--reference", "--json"],
        capture_output=True, text=True, env=env, timeout=300)
    assert a.returncode == 0, a.stderr
    b = subprocess.run(
        [sys.executable, "-m", "spfft_tpu.serve.store", "prewarm",
         root, "--compile", "--check-reference", "--strict", "--json"],
        capture_output=True, text=True, env=env, timeout=300)
    assert b.returncode == 0, b.stderr
    report = json.loads(b.stdout.strip().splitlines()[-1])
    assert report["builds"] == 0
    assert report["reference_bit_exact"] is True
    assert report["compile_events"]["compression_tables"] == 0


# -- degradation ladder: disk faults at the write seams ---------------------

def _no_tmp_files(store):
    leftovers = []
    for dirpath, _, names in os.walk(store.root):
        leftovers += [os.path.join(dirpath, n) for n in names
                      if n.startswith(".tmp-")]
    return leftovers


def test_store_transient_io_retry_succeeds(tmp_path, monkeypatch):
    """A transient OSError (EINTR-shaped) during the atomic write gets
    the bounded retry and the spill SUCCEEDS — no degradation, the
    retry is counted, and the artifact round-trips."""
    import errno

    store, reg, tr, sig, plan = _build_store(tmp_path)
    key = signature_key(sig)
    os.unlink(store.artifact_path(key))

    real_fsync = os.fsync
    fails = {"n": 0}

    def flaky_fsync(fd):
        if fails["n"] == 0:
            fails["n"] += 1
            raise OSError(errno.EINTR, "Interrupted system call")
        return real_fsync(fd)

    monkeypatch.setattr(store_mod.os, "fsync", flaky_fsync)
    assert store.save_plan(sig, plan) == key
    monkeypatch.undo()

    health = store.health()
    assert health["state"] == "ok"
    assert health["io_retries"] >= 1
    assert fails["n"] == 1
    assert os.path.exists(store.artifact_path(key))
    got = store.load_key(key)
    assert got is not None and signature_key(got[0]) == key
    assert not _no_tmp_files(store)


def test_store_enospc_mid_spill_degrades_to_memory_only(tmp_path):
    """An injected ENOSPC at the spill seam flips the store to the
    memory-only tier: the failing save raises typed OSError and leaves
    NO artifact and NO temp file; subsequent saves are skipped and
    counted under rejects{degraded}; a forced re-probe on a healthy
    volume lifts the degradation and spills resume."""
    from spfft_tpu import faults

    store, reg, tr, sig, plan = _build_store(tmp_path)
    key = signature_key(sig)
    os.unlink(store.artifact_path(key))
    try:
        faults.arm(faults.FaultPlan(script="store.spill@1:enospc"))
        with pytest.raises(OSError):
            store.save_plan(sig, plan)
    finally:
        faults.disarm()

    assert store.degraded
    health = store.health()
    assert health["state"] == "degraded" and health["mode"] == "memory-only"
    assert "InjectedDiskFull" in health["reason"]
    assert not os.path.exists(store.artifact_path(key))
    assert not _no_tmp_files(store)

    # degraded: the next save is skipped, typed-counted, still no file
    assert store.save_plan(sig, plan) == key
    assert store.stats()["rejects"].get("degraded", 0) >= 1
    assert not os.path.exists(store.artifact_path(key))

    # volume is actually fine (the fault was injected): a due re-probe
    # lifts the degradation and the same save goes to disk
    store._reprobe_at = 0.0
    assert store.save_plan(sig, plan) == key
    assert not store.degraded
    assert os.path.exists(store.artifact_path(key))
    got = store.load_key(key)
    assert got is not None and signature_key(got[0]) == key


def test_store_torn_write_leaves_no_partial_artifact(tmp_path):
    """A disk-full at the replace seam — after the temp file is fully
    written but before it lands — must never leave either a torn
    artifact or the orphan temp: the cleanup unlinks the temp, the
    store degrades, and verify() stays clean."""
    from spfft_tpu import faults

    store, reg, tr, sig, plan = _build_store(tmp_path)
    key = signature_key(sig)
    os.unlink(store.artifact_path(key))
    try:
        faults.arm(faults.FaultPlan(script="store.replace@1:enospc"))
        with pytest.raises(OSError):
            store.save_plan(sig, plan)
    finally:
        faults.disarm()

    assert store.degraded
    assert not os.path.exists(store.artifact_path(key))
    assert not _no_tmp_files(store)
    assert not [row for row in store.verify() if not row.get("ok")]


def test_store_read_only_directory_degrades_and_serving_continues(
        tmp_path, monkeypatch):
    """EROFS (a genuinely read-only volume, simulated at os.replace
    because tests run as root and chmod is advisory) classifies as a
    PERSISTENT disk fault: the store degrades, and the registry keeps
    building and serving plans from memory with spills skipped."""
    import errno

    store, reg, tr, sig, plan = _build_store(tmp_path)

    def erofs(src, dst):
        raise OSError(errno.EROFS, "Read-only file system")

    monkeypatch.setattr(store_mod.os, "replace", erofs)
    with pytest.raises(OSError):
        store.save_plan(sig, plan)
    monkeypatch.undo()

    assert store.degraded
    assert "Read-only" in store.health()["reason"]

    # serving continues: a fresh build succeeds, its spill is skipped
    sig2, plan2 = reg.get_or_build(TransformType.C2C, 16, 16, 16,
                                   _triplets(16))
    store.drain()
    assert reg.get(sig2) is plan2
    assert plan2.index_plan.num_values > 0
    assert store.stats()["rejects"].get("degraded", 0) >= 1
    assert not os.path.exists(store.artifact_path(signature_key(sig2)))


def test_gc_during_load_is_typed_and_rebuilds_clean(tmp_path):
    """Concurrent GC racing readers: every load_key result is either a
    full (signature, plan) or a clean miss (None) — never an exception,
    never a torn read — and after GC empties the store the registry
    rebuilds from scratch to the same signature."""
    store, reg, tr, sig, plan = _build_store(tmp_path)
    key = signature_key(sig)
    results, errors = [], []
    stop = threading.Event()

    def loader():
        for _ in range(500):
            if stop.is_set():
                break
            try:
                results.append(store.load_key(key))
            except BaseException as exc:  # noqa: BLE001 - the assertion
                errors.append(exc)
                break

    threads = [threading.Thread(target=loader) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(10):
            store.gc(max_bytes=1)
            store.save_plan(sig, plan)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    assert not errors, errors
    assert results
    for got in results:
        assert got is None or signature_key(got[0]) == key

    # empty the store for real: a miss, then a bit-exact clean rebuild
    store.gc(max_bytes=1)
    assert store.load_key(key) is None
    reg2 = PlanRegistry(store=store)
    sig3, plan3 = reg2.get_or_build(TransformType.C2C, DIM, DIM, DIM, tr)
    assert signature_key(sig3) == key
    np.testing.assert_array_equal(
        plan.index_plan.slot_src, plan3.index_plan.slot_src)
