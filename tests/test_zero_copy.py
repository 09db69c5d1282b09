"""Zero-copy batched data plane (the PR-6 tentpole) — slab semantics,
batched-vs-per-item equivalence, per-slab credit, planner slab rules,
and the host-compute-bound replan remedy.

The load-bearing property: ``batch_items=1`` is byte-for-byte the
historical per-item path, and any slab size produces the SAME delivered
items and the SAME stream checksum on every mover path (linear bulk,
DAG split, mirror).  The batched plane is an optimization, never a
semantic change.
"""

import hashlib
import os
import time

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, st

from repro.core.basin import DrainageBasin, GBPS, Link, Tier, TierKind
from repro.core.integrity import StreamDigest
from repro.core.mover import MoverConfig, UnifiedDataMover
from repro.core.planner import (SLAB_TARGET_BYTES, plan_delta, plan_transfer,
                                replan)
from repro.core.staging import (StagePipeline, StageReport, WindowedStage,
                                slab_views)

ITEM = 8 * 1024


def _linear_basin():
    return DrainageBasin([
        Tier("src", TierKind.SOURCE, 10.0 * GBPS, latency_s=1e-6),
        Tier("buf", TierKind.BURST_BUFFER, 50.0 * GBPS, latency_s=1e-6),
        Tier("dst", TierKind.SINK, 20.0 * GBPS, latency_s=1e-6),
    ])


def _fanout_basin():
    tiers = [
        Tier("src", TierKind.SOURCE, 40.0 * GBPS, latency_s=1e-6),
        Tier("staging", TierKind.BURST_BUFFER, 40.0 * GBPS, latency_s=1e-6),
        Tier("path-a", TierKind.SINK, 10.0 * GBPS),
        Tier("path-b", TierKind.SINK, 10.0 * GBPS),
    ]
    return DrainageBasin(tiers, [Link("src", "staging"),
                                 Link("staging", "path-a"),
                                 Link("staging", "path-b")])


def _xor_sha256(items):
    acc = bytearray(32)
    for it in items:
        d = hashlib.sha256(bytes(it)).digest()
        for i in range(32):
            acc[i] ^= d[i]
    return bytes(acc).hex()


# -- slab_views: the zero-copy item stream -----------------------------------

def test_slab_views_share_storage_with_the_buffer():
    buf = bytearray(os.urandom(4 * ITEM))
    views = list(slab_views(buf, ITEM))
    assert all(isinstance(v, memoryview) for v in views)
    assert sum(len(v) for v in views) == len(buf)
    # zero-copy means SHARED storage: mutating the buffer is visible
    # through every previously-yielded view
    buf[0] ^= 0xFF
    assert views[0][0] == buf[0]


def test_slab_views_short_last_slice():
    buf = bytes(os.urandom(2 * ITEM + 100))
    views = list(slab_views(buf, ITEM))
    assert [len(v) for v in views] == [ITEM, ITEM, 100]
    assert b"".join(bytes(v) for v in views) == buf


def test_slab_views_rejects_nonpositive_item_bytes():
    with pytest.raises(ValueError):
        list(slab_views(b"x", 0))
    with pytest.raises(ValueError):
        list(slab_views(b"x", -1))


# -- S3: slab path is bit-identical to the per-item path ---------------------
#
# The equivalence property, on every mover path.  Payloads are os.urandom
# so no two items collide: the XOR-folded stream checksum would cancel
# identical items appearing an even number of times, masking a dropped
# or duplicated pair.  Distinct payloads make the checksum injective
# enough that "same digest" really means "same multiset of items".

def _run_linear(payloads, plan, batch_items):
    got = []
    mover = UnifiedDataMover(MoverConfig(checksum=True), plan=plan)
    rep = mover.bulk_transfer(
        iter(payloads), got.append,
        transforms=[("pull", None), ("push", None)],
        checksum=True, batch_items=batch_items)
    return rep, got


@settings(max_examples=5)
@given(n_items=st.integers(min_value=3, max_value=96),
       batch=st.integers(min_value=2, max_value=16))
def test_linear_slab_path_matches_per_item_path(n_items, batch):
    payloads = [os.urandom(ITEM) for _ in range(n_items)]
    plan = plan_transfer(_linear_basin(), ITEM, stages=("pull", "push"),
                         checksum=True, batch_items=batch)
    rep1, got1 = _run_linear(payloads, plan, 1)
    repb, gotb = _run_linear(payloads, plan, None)
    assert rep1.items == repb.items == n_items
    assert rep1.checksum == repb.checksum == _xor_sha256(payloads)
    # per-item order survives (single pipeline); the batched path
    # delivers the same multiset — put_many keeps slab order, but worker
    # interleaving across slabs may reorder, exactly like per-item
    assert sorted(got1) == sorted(gotb) == sorted(payloads)


def _run_parallel(payloads, plan, mode, route, batch_items):
    mover = UnifiedDataMover(MoverConfig(checksum=True), plan=plan)
    rep = mover.parallel_transfer(
        iter(payloads), lambda _: None, mode=mode, route=route,
        checksum=True, batch_items=batch_items)
    return rep


@pytest.mark.parametrize("mode,route", [("split", "deal"),
                                        ("split", "steal"),
                                        ("mirror", "deal")])
def test_dag_slab_path_matches_per_item_path(mode, route):
    n = 64
    payloads = [os.urandom(ITEM) for _ in range(n)]
    plan = plan_transfer(_fanout_basin(), ITEM, stages=("deliver",),
                         checksum=True, batch_items=8)
    rep1 = _run_parallel(payloads, plan, mode, route, 1)
    repb = _run_parallel(payloads, plan, mode, route, None)
    expect = n if mode == "split" else 2 * n    # mirror counts deliveries
    assert rep1.items == repb.items == expect
    # each source item hashed ONCE in both modes and both planes
    assert rep1.checksum == repb.checksum == _xor_sha256(payloads)


# -- per-slab credit under the windowed stage --------------------------------

def test_windowed_stage_slab_admission_respects_credit():
    """A slab wider than the window must wave through the ACK ledger —
    stall_window_s accrues, nothing is dropped, and the checksum of what
    came out matches what went in."""
    n, size = 24, 1024
    payloads = [os.urandom(size) for _ in range(n)]
    stage = WindowedStage("wire", window_bytes=2 * size, rtt_s=2e-3,
                          capacity=16, workers=1, batch_items=8)
    pipe = StagePipeline(iter(payloads), [stage]).start()
    got = list(pipe)
    rep = stage.report()
    assert len(got) == n and rep.items == n
    assert _xor_sha256(got) == _xor_sha256(payloads)
    # 8-item slabs against a 2-item window: credit waits are mandatory
    assert rep.stall_window_s > 0.0
    # the ledger balances once the last ACK matures (one RTT after the
    # final transmission)
    time.sleep(0.02)
    assert stage.inflight_bytes == 0.0


def test_windowed_plan_clamps_slab_to_window():
    basin = DrainageBasin([
        Tier("src", TierKind.SOURCE, 10.0 * GBPS, latency_s=1e-6),
        Tier("wan", TierKind.CHANNEL, 10.0 * GBPS, latency_s=5e-3),
        Tier("dst", TierKind.SINK, 10.0 * GBPS, latency_s=1e-6),
    ])
    plan = plan_transfer(basin, ITEM, stages=("send", "recv"),
                         batch_items="auto")
    for h in plan.hops:
        if h.window_bytes > 0:
            # a single slab admission must never park the whole pool on
            # the ACK clock
            assert h.batch_items * ITEM <= h.window_bytes


# -- planner slab rules ------------------------------------------------------

def test_auto_batch_targets_slab_bytes():
    plan = plan_transfer(_linear_basin(), ITEM, stages=("pull", "push"),
                         batch_items="auto")
    for h in plan.hops:
        assert h.batch_items > 1
        assert h.batch_items <= SLAB_TARGET_BYTES // ITEM
        # double-buffered slabs: the buffer holds two
        assert h.capacity >= 2 * h.batch_items


def test_default_plan_stays_per_item():
    plan = plan_transfer(_linear_basin(), ITEM, stages=("pull", "push"))
    assert all(h.batch_items == 1 for h in plan.hops)


def test_ordered_plan_pins_per_item():
    plan = plan_transfer(_linear_basin(), ITEM, stages=("pull", "push"),
                         ordered=True, batch_items="auto")
    assert all(h.batch_items == 1 for h in plan.hops)


def test_pinned_batch_and_invalid_batch():
    plan = plan_transfer(_linear_basin(), ITEM, stages=("pull",),
                         batch_items=4)
    assert all(h.batch_items == 4 for h in plan.hops)
    with pytest.raises(ValueError):
        plan_transfer(_linear_basin(), ITEM, stages=("pull",), batch_items=0)


def test_plan_delta_carries_batch_revision():
    old = plan_transfer(_linear_basin(), ITEM, stages=("pull", "push"))
    new = plan_transfer(_linear_basin(), ITEM, stages=("pull", "push"),
                        batch_items=16)
    delta = plan_delta(old, new)
    assert delta
    assert all(delta.hops[h.name].batch_items == 16 for h in new.hops)


def test_describe_shows_slab_and_placement():
    plan = plan_transfer(_linear_basin(), ITEM, stages=("pull", "push"),
                         checksum=True, batch_items="auto")
    desc = plan.describe()
    assert f"b={plan.hops[0].batch_items}" in desc
    assert ":host" in desc
    accel = plan_transfer(_linear_basin(), ITEM, stages=("pull", "push"),
                          checksum=True, checksum_placement="accel")
    assert ":accel" in accel.describe()


# -- host-compute-bound: the digest-placement verdict ------------------------

def _pinned_report(plan):
    """The checksum hop delivering exactly at the modeled host hash
    ceiling: no stall on any side, far under the hop's promise."""
    hop = plan.hops[plan.checksum_index]
    # for the accel twin the ceiling is far above line rate; pin the
    # report at the HOST ceiling either way, so the two placements see
    # the same delivered bytes
    rate = min(hop.digest_bytes_per_s or 0.2 * GBPS, 0.2 * GBPS)
    return StageReport(name=hop.name, items=5798,
                       bytes=int(rate * 1.9), elapsed_s=2.0, active_s=2.0,
                       stall_up_s=0.02, stall_down_s=0.02,
                       stall_window_s=0.0, errors=0)


def test_host_placed_digest_pin_flips_placement_only():
    plan = plan_transfer(_linear_basin(), ITEM, stages=("pull", "push"),
                         checksum=True, checksum_placement="host",
                         host_digest_bytes_per_s=0.2 * GBPS)
    hop = plan.hops[plan.checksum_index]
    revised = replan(plan, [_pinned_report(plan)], damping=1.0)
    assert revised.diagnosis == {
        hop.name: f"host-compute-bound({hop.up_tier}:digest)"}
    assert revised.checksum_placement == "accel"
    # the remedy is placement, NOT estimates: promise and staffing stand
    assert revised.planned_bytes_per_s == pytest.approx(
        plan.planned_bytes_per_s)
    assert [(h.workers, h.capacity) for h in revised.hops] == \
        [(h.workers, h.capacity) for h in plan.hops]


def test_accel_placed_digest_never_reads_as_compute_bound():
    plan = plan_transfer(_linear_basin(), ITEM, stages=("pull", "push"),
                         checksum=True, checksum_placement="accel")
    # identical starved-looking report; the accel digest ceiling sits far
    # above the hop promise, so the compute verdict cannot fire
    revised = replan(plan, [_pinned_report(plan)], damping=1.0)
    assert not any("host-compute" in v for v in revised.diagnosis.values())
    assert revised.checksum_placement == "accel"


# -- digest formats and slab folding -----------------------------------------

def test_host_digest_matches_historical_xor_of_sha256():
    items = [os.urandom(256) for _ in range(9)]
    d = StreamDigest(True, placement="host")
    for it in items:
        d.add(it)
    assert d.hexdigest() == _xor_sha256(items)


def test_slab_fold_equals_per_item_fold():
    items = [os.urandom(300) for _ in range(17)]
    one, many = (StreamDigest(True, placement="host"),
                 StreamDigest(True, placement="host"))
    for it in items:
        one(it)                    # __call__ is the per-item transform
    out = many.many(items)         # .many is the slab hook
    assert list(out) == items      # transforms pass items through
    assert one.hexdigest() == many.hexdigest()


def test_accel_digest_pallas_matches_ref_backend():
    """The Pallas digest kernel (interpreted off the TPU) and the jnp
    oracle give the same bits on a stream's panels, so the accel checksum
    does not depend on which of the two computes it."""
    import numpy as np
    from repro.core.integrity import _item_words
    from repro.kernels import ops
    from repro.kernels.digest import digest_ref
    items = [os.urandom(ITEM) for _ in range(5)] + [os.urandom(37)]
    # 41 block rows: not a whole number of kernel tiles, so the pad runs
    panels = np.concatenate([_item_words(it)[0] for it in items])
    assert np.array_equal(np.asarray(ops.block_digest(panels)),
                          np.asarray(digest_ref(panels)))
    d = StreamDigest(True, placement="accel")
    d.many(items)
    assert d.hexdigest().startswith("u32:")


def test_disabled_digest_is_a_noop():
    d = StreamDigest(False)
    assert d.add(b"x") == b"x" and d.many([b"y"]) == [b"y"]
    assert d.hexdigest() is None


def test_compress_transform_roundtrip_with_slab_hook():
    import numpy as np
    from repro.core.integrity import compress_transform, decompress_transform
    comp, decomp = compress_transform(), decompress_transform()
    xs = [np.random.default_rng(i).normal(size=(8, 256)).astype("float32")
          * 3.0 for i in range(3)]
    # the slab hook exists (what the batched worker loop discovers) and
    # agrees with the per-item form
    per_item = [decomp(comp(x)) for x in xs]
    slab = list(decomp.many(comp.many(xs)))
    for a, b, x in zip(per_item, slab, xs):
        assert np.allclose(a, b)
        assert float(np.abs(a - x).max()) / 3.0 < 2.0 / 127.0 * 3.0
