"""JCCL collective correctness — with and without failure injection."""

import numpy as np
import pytest

from hyp_compat import given, settings, st

from repro.core import shift as S
from repro.core import verbs as V
from repro.core.fabric import build_cluster
from repro.collectives import JcclWorld, CollectiveError


def make_world(n_ranks=4, lib_kind="shift", nics_per_host=2,
               probe_interval=5e-3, max_chunk_bytes=1 << 16):
    c = build_cluster(n_hosts=n_ranks, nics_per_host=nics_per_host)
    if lib_kind == "shift":
        cfg = S.ShiftConfig(probe_interval=probe_interval)
        kv = None
        libs = []
        for r in range(n_ranks):
            lib = S.ShiftLib(c, f"host{r}", kv=kv, config=cfg)
            kv = lib.kv
            libs.append(lib)
    else:
        libs = [S.StandardLib(c, f"host{r}") for r in range(n_ranks)]
    world = JcclWorld(c, libs, max_chunk_bytes=max_chunk_bytes)
    return c, world


def test_allreduce_exact_small():
    c, w = make_world(n_ranks=4)
    arrays = [np.arange(1000, dtype=np.int64) * (r + 1) for r in range(4)]
    expect = sum(a.copy() for a in arrays)
    w.allreduce(arrays)
    for a in arrays:
        np.testing.assert_array_equal(a, expect)


def test_allreduce_multibucket():
    c, w = make_world(n_ranks=2, max_chunk_bytes=4096)
    n = 4096 * 5 + 37  # forces several buckets + ragged tail
    arrays = [np.ones(n, dtype=np.float32) * (r + 1) for r in range(2)]
    w.allreduce(arrays)
    for a in arrays:
        np.testing.assert_allclose(a, 3.0)


def test_allgather_and_broadcast_and_a2a():
    c, w = make_world(n_ranks=4)
    shards = [np.full(17 + r, r, dtype=np.float32) for r in range(4)]
    full = w.all_gather(shards)
    expect = np.concatenate(shards)
    for f in full:
        np.testing.assert_array_equal(f, expect)

    msg = np.arange(5000, dtype=np.float32)
    outs = w.broadcast(msg, root=2)
    for o in outs:
        np.testing.assert_array_equal(o, msg)

    mats = [np.arange(4 * 8, dtype=np.int64).reshape(4, 8) + 100 * r
            for r in range(4)]
    outs = w.all_to_all(mats)
    for j in range(4):
        for i in range(4):
            np.testing.assert_array_equal(outs[j][i], mats[i][j])


@pytest.mark.parametrize("fault", [False, True])
def test_allgather_shards_larger_than_a_chunk(fault):
    # a serving logits shard (vocab slice x batch) can exceed the wire
    # chunk: each shard must travel as max_chunk_bytes pieces
    c, w = make_world(n_ranks=3, max_chunk_bytes=4096)
    shards = [np.arange(4096 // 4 * 5 + 7 * r, dtype=np.float32) + 1e4 * r
              for r in range(3)]
    if fault:
        c.sim.at(c.sim.now + 2e-5, c.fail_nic, "host1/mlx5_0")
    full = w.all_gather(shards)
    expect = np.concatenate(shards)
    for f in full:
        np.testing.assert_array_equal(f, expect)
    fallbacks = sum(ep.lib.stats.fallbacks for ep in w.endpoints)
    assert (fallbacks > 0) == fault


def test_reduce_scatter_owned_chunks():
    c, w = make_world(n_ranks=4)
    arrays = [np.arange(64, dtype=np.int64) for _ in range(4)]
    owned = w.reduce_scatter(arrays)
    full = np.arange(64, dtype=np.int64) * 4
    per = 16
    for r in range(4):
        own = (r + 1) % 4
        np.testing.assert_array_equal(owned[r], full[own * per:(own + 1) * per])


def test_allreduce_survives_nic_failure_mid_collective():
    c, w = make_world(n_ranks=4, max_chunk_bytes=8192)
    n = 8192 * 6  # enough steps that the failure lands mid-collective
    arrays = [np.ones(n, dtype=np.float64) * (r + 1) for r in range(4)]
    # kill host1's rail-0 NIC shortly after the collective starts (the
    # bucket-parallel rings finish in ~230us of virtual time, so the
    # fault must land well inside that window)
    c.sim.at(c.sim.now + 1e-4, c.fail_nic, "host1/mlx5_0")
    w.allreduce(arrays)
    for a in arrays:
        np.testing.assert_allclose(a, 10.0)
    assert any(lib.stats.fallbacks > 0
               for lib in (ep.lib for ep in w.endpoints))


def test_allreduce_survives_flap_and_switches_back():
    c, w = make_world(n_ranks=2, max_chunk_bytes=8192, probe_interval=2e-3)
    n = 8192 * 8
    arrays = [np.full(n, float(r + 1), dtype=np.float64) for r in range(2)]
    t0 = c.sim.now
    c.flap_nic("host0/mlx5_0", down_at=t0 + 2e-4, up_at=t0 + 8e-3)
    w.allreduce(arrays)
    for a in arrays:
        np.testing.assert_allclose(a, 3.0)
    # run on; a later collective should use the recovered default path
    c.sim.run(until=c.sim.now + 0.1)
    arrays2 = [np.full(1024, float(r + 1), dtype=np.float64) for r in range(2)]
    w.allreduce(arrays2)
    for a in arrays2:
        np.testing.assert_allclose(a, 3.0)
    libs = [ep.lib for ep in w.endpoints]
    assert any(lib.stats.recoveries > 0 for lib in libs)


def test_standard_world_aborts_on_failure():
    c, w = make_world(n_ranks=2, lib_kind="standard", max_chunk_bytes=8192)
    n = 8192 * 8
    arrays = [np.ones(n, dtype=np.float64) for _ in range(2)]
    c.sim.at(c.sim.now + 2e-4, c.fail_nic, "host1/mlx5_0")
    with pytest.raises(CollectiveError):
        w.allreduce(arrays, timeout=5.0)


@given(fail_t=st.floats(min_value=5e-5, max_value=2e-3),
       victim=st.sampled_from(["host0/mlx5_0", "host1/mlx5_0",
                               "host2/mlx5_0"]))
@settings(max_examples=10, deadline=None)
def test_allreduce_exact_under_any_failure_timing(fail_t, victim):
    """Property: the all-reduce result is bit-exact no matter when (or
    which) NIC dies — SHIFT's §3.2 guarantee at collective level."""
    V.reset_registries()
    c, w = make_world(n_ranks=3, max_chunk_bytes=4096)
    n = 4096 * 4
    arrays = [(np.arange(n, dtype=np.int64) % 97) * (r + 1) for r in range(3)]
    expect = sum(a.copy() for a in arrays)
    c.sim.at(c.sim.now + fail_t, c.fail_nic, victim)
    w.allreduce(arrays)
    for a in arrays:
        np.testing.assert_array_equal(a, expect)
