"""Fast-datapath equivalence + simulator compaction tests (DESIGN.md §5).

The coalescing zero-copy datapath (``Cluster.fast_datapath=True``) must
be byte-identical to the legacy per-WQE copying path for every opcode,
and the overhauled simulator must keep cancelled events from leaking.
"""

import numpy as np
import pytest

from hyp_compat import given, settings, st

from repro.core import verbs as V
from repro.core.fabric import Simulator, build_cluster
from repro.scenarios import SCENARIOS
from repro.scenarios.engine import make_pair, run_scenario


# ---------------------------------------------------------------------------
# simulator: tuple records, call(), lazy-deletion compaction
# ---------------------------------------------------------------------------


def test_simulator_call_and_schedule_interleave_in_order():
    sim = Simulator()
    out = []
    sim.schedule(2e-3, out.append, "b")
    sim.call(1e-3, out.append, "a")
    sim.call(3e-3, out.append, "c")
    sim.run_until_idle()
    assert out == ["a", "b", "c"]
    assert sim._executed == 3


def test_cancelled_events_do_not_fire_and_heap_compacts():
    sim = Simulator()
    out = []
    evs = [sim.schedule(1.0 + i * 1e-6, out.append, i) for i in range(500)]
    for ev in evs[:499]:
        ev.cancel()
    # compaction triggers once dead events exceed half the heap
    sim.schedule(2.0, out.append, "tail")
    assert len(sim._heap) < 500, "cancel leak: dead events linger in heap"
    assert sim._compactions >= 1
    sim.run_until_idle()
    assert out == [499, "tail"]


def test_cancel_after_fire_is_a_noop():
    """Cancelling an event that already executed must not inflate the
    dead-event count (which would trigger no-op compactions)."""
    sim = Simulator()
    ev = sim.schedule(1e-3, lambda: None)
    sim.run_until_idle()
    ev.cancel()
    assert sim._dead == 0 and not ev.cancelled


def test_peek_time_skips_cancelled():
    sim = Simulator()
    ev = sim.schedule(1.0, lambda: None)
    sim.schedule(2.0, lambda: None)
    ev.cancel()
    assert sim.peek_time() == pytest.approx(2.0)


def test_compaction_during_run_keeps_future_events():
    """Regression: compaction must rebuild the heap IN PLACE — run()
    holds a reference to the heap list across events."""
    sim = Simulator()
    fired = []

    def schedule_more():
        # force a compaction while run() is mid-loop...
        evs = [sim.schedule(5.0, fired.append, -1) for _ in range(200)]
        for ev in evs:
            ev.cancel()
        sim.schedule(1e-3, fired.append, "later")  # triggers compaction

    sim.schedule(0.0, schedule_more)
    sim.run_until_idle()
    # ...and the event scheduled after compaction must still fire
    assert fired == ["later"]


# ---------------------------------------------------------------------------
# byte-identical delivery: fast vs legacy across opcodes
# ---------------------------------------------------------------------------


def _run_script(fast, script):
    """Execute a list of (op, size, src_off, dst_off) transfers on a fresh
    standard pair; returns (dst bytes, src bytes, wc stream, recv stream)."""
    c, a, b = make_pair("standard", fast=fast,
                        endpoint_kw={"buf_size": 1 << 16})
    rng = np.random.RandomState(1234)
    a.buf[:] = rng.randint(0, 256, a.buf.size, dtype=np.uint8)
    b.buf[:] = rng.randint(0, 256, b.buf.size, dtype=np.uint8)
    wrs = []
    for i, (op, size, s_off, d_off) in enumerate(script):
        if op in ("SEND", "WRITE_IMM"):
            b.lib.post_recv(b.qp, V.RecvWR(
                wr_id=1000 + i, sge=V.SGE(b.mr.addr + d_off, size,
                                          b.mr.lkey)))
        if op in ("FETCH_ADD", "CMP_SWAP"):
            wrs.append(V.SendWR(
                wr_id=i, opcode=V.Opcode[op],
                sge=V.SGE(a.mr.addr + s_off, 8, a.mr.lkey),
                remote_addr=b.mr.addr + (d_off & ~7), rkey=b.mr.rkey,
                compare_add=3, swap=7))
        else:
            wrs.append(V.SendWR(
                wr_id=i, opcode=V.Opcode[op],
                sge=V.SGE(a.mr.addr + s_off, size, a.mr.lkey),
                remote_addr=b.mr.addr + d_off, rkey=b.mr.rkey,
                imm_data=i))
    # mix posting styles: chain the first half, post the rest singly
    half = len(wrs) // 2
    if half:
        a.lib.post_send_chain(a.qp, wrs[:half])
    for wr in wrs[half:]:
        a.lib.post_send(a.qp, wr)
    c.sim.run(until=c.sim.now + 1.0)
    send_wcs = a.poll()
    recv_wcs = b.poll()
    return (bytes(b.buf.tobytes()), bytes(a.buf.tobytes()),
            [(w.wr_id, w.status, w.opcode) for w in send_wcs],
            [(w.wr_id, w.status, w.opcode, w.imm_data, w.byte_len)
             for w in recv_wcs])


OPS = ["WRITE", "WRITE_IMM", "SEND", "READ", "FETCH_ADD", "CMP_SWAP"]


def test_all_opcodes_byte_identical_fast_vs_legacy():
    script = []
    for i, op in enumerate(OPS * 4):
        size = 64 + 32 * i
        script.append((op, size, (i * 256) % 8192, (i * 512) % 16384))
    slow = _run_script(False, script)
    fast = _run_script(True, script)
    assert fast[0] == slow[0], "destination memory differs"
    assert fast[1] == slow[1], "source memory differs (READ/atomic returns)"
    assert fast[2] == slow[2], "send WC stream differs"
    assert fast[3] == slow[3], "recv WC stream differs"


def test_write_ack_does_not_overtake_read_response():
    # a long READ response still serializes when the next WRITE's ACK is
    # sent; RC completions must still leave in posting order on both paths
    script = [("READ", 1884, 0, 0), ("WRITE", 8, 0, 0)]
    slow = _run_script(False, script)
    assert [wr_id for wr_id, _, _ in slow[2]] == [0, 1]
    assert _run_script(True, script) == slow


@given(st.lists(st.tuples(st.sampled_from(OPS),
                          st.integers(min_value=8, max_value=2048),
                          st.integers(min_value=0, max_value=50),
                          st.integers(min_value=0, max_value=50)),
                min_size=1, max_size=24))
@settings(max_examples=20, deadline=None)
def test_property_fast_vs_legacy_byte_identical(raw):
    script = [(op, size, s * 128, d * 128) for op, size, s, d in raw]
    slow = _run_script(False, script)
    fast = _run_script(True, script)
    assert fast == slow


def test_chain_post_equals_single_posts():
    """A posted WR chain must deliver exactly like sequential posts."""
    script = [("WRITE", 512, i * 512, i * 512) for i in range(12)]
    c, a, b = make_pair("standard", fast=True,
                        endpoint_kw={"buf_size": 1 << 16})
    a.buf[:] = 7
    wrs = [V.SendWR(wr_id=i, opcode=V.Opcode.WRITE,
                    sge=V.SGE(a.mr.addr + s, n, a.mr.lkey),
                    remote_addr=b.mr.addr + d, rkey=b.mr.rkey)
           for i, (_, n, s, d) in enumerate(script)]
    a.lib.post_send_chain(a.qp, wrs)
    c.sim.run_until_idle()
    wcs = a.poll()
    assert [w.wr_id for w in wcs] == list(range(12))
    assert all(w.status is V.WCStatus.SUCCESS for w in wcs)
    assert (b.buf[:12 * 512] == 7).all()


# ---------------------------------------------------------------------------
# zero-copy semantics
# ---------------------------------------------------------------------------


def test_ro_view_is_read_only():
    c = build_cluster()
    ctx = V.ibv_open_device(c, "host0", "mlx5_0")
    pd = V.ibv_alloc_pd(ctx)
    buf = np.zeros(4096, dtype=np.uint8)
    mr = V.ibv_reg_mr(pd, buf)
    view = mr.ro_view(mr.addr, 128)
    with pytest.raises(ValueError):
        view[0] = 1
    # the writable path still works
    mr.slice(mr.addr, 128)[0] = 9
    assert view[0] == 9  # same memory, zero copies


def test_sq_ring_is_bounded():
    """The send queue is a true ring: memory stays O(cap) no matter how
    many WRs stream through it (O(1) ring-index bookkeeping)."""
    c, a, b = make_pair("standard", fast=True,
                        endpoint_kw={"buf_size": 1 << 16})
    cap = a.qp.cap.max_send_wr
    n = cap * 2 + 37
    for i in range(n):
        V.ibv_post_send(a.qp, V.SendWR(
            wr_id=i, opcode=V.Opcode.WRITE,
            sge=V.SGE(a.mr.addr, 64, a.mr.lkey),
            remote_addr=b.mr.addr, rkey=b.mr.rkey))
        if i % 64 == 0:
            c.sim.run(until=c.sim.now + 1e-3)
    c.sim.run_until_idle()
    assert len(a.qp.sq) <= cap
    assert a.qp.sq_tail == n
    wcs = a.poll(n + 1)
    assert len(wcs) == n and all(not w.is_error for w in wcs)


def test_backup_failure_with_only_unsignaled_outstanding_propagates():
    """Unsignaled sends are not in wqe_map; a backup-NIC death while only
    unsignaled WRs are outstanding must still reach _propagate_errors
    (regression: the flushed error WCs were silently swallowed)."""
    from repro.core.shift import SendState

    c, a, b = make_pair("shift", probe_interval=50e-3)
    # in-flight signaled traffic, then kill the default NIC -> fallback
    a.lib.post_send(a.qp, V.SendWR(
        wr_id=99, opcode=V.Opcode.WRITE,
        sge=V.SGE(a.mr.addr, 4096, a.mr.lkey),
        remote_addr=b.mr.addr, rkey=b.mr.rkey))
    c.fail_nic("host0/mlx5_0")
    c.sim.run(until=c.sim.now + 5e-3)
    assert a.lib.stats.fallbacks >= 1
    # post ONLY unsignaled writes (never mapped in wqe_map)...
    for i in range(4):
        a.lib.post_send(a.qp, V.SendWR(
            wr_id=i, opcode=V.Opcode.WRITE,
            sge=V.SGE(a.mr.addr, 4096, a.mr.lkey),
            remote_addr=b.mr.addr, rkey=b.mr.rkey, send_flags=0))
    # ...then cut the backup LINK mid-flight (NIC stays up, so the idle
    # control QP raises no error of its own): the data WQEs exhaust the
    # RC retry budget and their flush WCs are the ONLY failure signal
    c.fail_link("host0/mlx5_1")
    c.sim.run(until=c.sim.now + 50e-3)
    assert a.lib.stats.errors_propagated >= 1
    assert a.qp.send_state is SendState.FAILED


# ---------------------------------------------------------------------------
# campaign invariants in fast mode (all 14 scenarios)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_fast_mode_campaign_invariants(name):
    """Zero-copy / exactly-once / ordering invariants must stay green on
    the coalescing datapath with burst posting for every scenario."""
    r = run_scenario(SCENARIOS[name], fast=True, burst=8)
    assert r.ok, r.violations


def test_fast_and_legacy_campaign_delivery_traces_match():
    """Same scenario, same seed: the delivered-notification trace is
    identical across datapaths (timing may differ, content may not)."""
    for name in ("baseline_clean", "sender_nic_down", "nic_down_permanent"):
        slow = run_scenario(SCENARIOS[name], fast=False, burst=1)
        fast = run_scenario(SCENARIOS[name], fast=True, burst=8)
        assert slow.ok and fast.ok
        assert fast.delivered == slow.delivered
        assert fast.payload_mismatches == slow.payload_mismatches == 0
