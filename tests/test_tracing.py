"""The program's tracer (``repro.tracing``): nesting, self time, intervals
across calls, counters on the innermost span, window filtering and the
ring's bound; then, at tiny sizes on the CPU, the spans and byte counts
that the DDP trainer and the TP server record."""

import time

import jax
import numpy as np
import pytest

from repro import configs as C
from repro import tracing
from repro.collectives import JcclWorld, build_world
from repro.configs import gpt2_124m
from repro.core import shift as S
from repro.core.fabric import build_cluster
from repro.models import build_model
from repro.serving import RequestScheduler, TPServeEngine
from repro.train.trainer import DDPTrainer, TrainerConfig


def _named(recs, name):
    return [r for r in recs if r[0] == name]


def test_nesting_sets_parent_ids():
    tr = tracing.Tracer()
    with tr.span("outer", step=3) as outer:
        with tr.span("inner") as inner:
            pass
        with tr.span("inner"):
            with tr.span("leaf"):
                pass
    recs = tr.records()
    (o,) = _named(recs, "outer")
    kids = _named(recs, "inner")
    (leaf,) = _named(recs, "leaf")
    assert o[2] is None and o[5] == {"step": 3}
    assert [k[2] for k in kids] == [outer.id, outer.id]
    assert leaf[2] == kids[1][1] and kids[0][1] == inner.id
    assert o[3] <= kids[0][3] <= kids[0][4] <= o[4]
    assert outer.seconds == pytest.approx(o[4] - o[3])


def test_self_time_is_span_less_children():
    tr = tracing.Tracer()
    lo = time.perf_counter()
    with tr.span("parent") as p:
        time.sleep(0.01)
        with tr.span("child") as c:
            time.sleep(0.02)
    hi = time.perf_counter()
    assert tr.total("parent", lo, hi) == pytest.approx(p.seconds)
    assert tr.self_time("parent", lo, hi) == pytest.approx(
        p.seconds - c.seconds)
    assert 0.005 < tr.self_time("parent", lo, hi) < p.seconds - 0.015


def test_begin_end_across_calls_is_not_mirrored():
    tr = tracing.Tracer()
    with tr.span("tick") as tick:
        h = tr.begin("queued", rid=7)
    assert "queued" not in tr.stack_names()
    (open_,) = _named(tr.records(), "queued")
    assert open_[4] is None and open_[2] == tick.id
    with tr.span("later"):
        tr.add(d2h_bytes=5)              # lands on "later", not the interval
        tr.end(h, failed=0)
    (q,) = _named(tr.records(), "queued")
    assert q[4] is not None and q[5] == {"rid": 7, "failed": 0}
    with pytest.raises(ValueError):
        tr.end(h)


def test_add_lands_on_the_innermost_span_and_rid_is_inherited():
    tr = tracing.Tracer()
    tr.add(d2h_bytes=1)                  # no span open: dropped
    with tr.span("admit", rid=4):
        tr.add(h2d_bytes=2)
        with tr.span("copy"):
            tr.add(d2h_bytes=10)
            tr.add(d2h_bytes=5)
    recs = tr.records()
    assert _named(recs, "copy")[0][5] == {"rid": 4, "d2h_bytes": 15}
    assert _named(recs, "admit")[0][5] == {"rid": 4, "h2d_bytes": 2}
    lo, hi = recs[0][3] - 1, recs[-1][4] + 1
    assert tr.summed("admit", "d2h_bytes", lo, hi) == 15
    assert tr.summed("copy", "h2d_bytes", lo, hi) == 0


def test_window_filtering():
    tr = tracing.Tracer()
    with tr.span("a"):
        pass
    mid = time.perf_counter()
    with tr.span("a") as second:
        tr.add(n=1)
    with tr.span("b"):
        pass
    assert [r[1] for r in tr.records(mid)] == [second.id,
                                              second.id + 1]
    assert tr.total("a", mid, time.perf_counter()) == pytest.approx(
        second.seconds)
    assert tr.summed("a", "n", mid, time.perf_counter()) == 1
    # clipped to the window: only the part after ``cut``
    cut = (second.t0 + second.t1) / 2
    assert tr.total("a", cut, second.t1) == pytest.approx(second.t1 - cut)


def test_ring_bound_and_readers_refuse_after_a_drop():
    tr = tracing.Tracer(ring=4)
    early = time.perf_counter()
    for _ in range(3):
        with tr.span("s"):
            pass
    lo = time.perf_counter()
    for _ in range(3):
        with tr.span("s"):
            pass
    # six records through a ring of four: two dropped, both before ``lo``
    assert len(tr.records(lo)) == 3
    assert tr.total("s", lo, time.perf_counter()) is not None
    # the dropped ones ended after ``early``: every reader says so
    assert tr.records() is None and tr.records(early) is None
    assert tr.total("s", early, lo) is None
    assert tr.summed("s", "n", early, lo) is None
    assert tr.self_time("s", early, lo) is None


def test_stack_names_lists_mirrored_spans():
    tr = tracing.Tracer()
    with tr.span("x"):
        with tr.span("y"):
            pass
    tr.end(tr.begin("z"))
    assert tr.stack_names() == {"x", "y"}


def _ddp(tmp_path, steps=2):
    cluster = build_cluster(n_hosts=2, nics_per_host=2)
    kv, libs = None, []
    for r in range(2):
        lib = S.ShiftLib(cluster, f"host{r}", kv=kv)
        kv = lib.kv
        libs.append(lib)
    world = JcclWorld(cluster, libs, max_chunk_bytes=1 << 16)
    cfg = C.smoke_config("gpt2-124m", n_layers=1, d_model=64, n_heads=4,
                         n_kv_heads=4, d_ff=128, vocab=128)
    tcfg = TrainerConfig(steps=steps, ckpt_every=steps, lr=1e-3,
                         ckpt_dir=str(tmp_path / "ck"))
    return DDPTrainer(cluster, libs, cfg, tcfg, batch_per_rank=2,
                      seq_len=16), world


def test_ddp_trainer_spans_count_the_gradient_bytes(tmp_path):
    trainer, world = _ddp(tmp_path)
    lo = time.perf_counter()
    trainer.train(world)
    hi = time.perf_counter()
    params = jax.eval_shape(trainer.model.init, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    recs = tracing.records(lo, hi)
    steps = _named(recs, "trainer.step")
    assert [s[5]["step"] for s in steps] == [1, 2]
    g2h = _named(recs, "trainer.grads_to_host")
    assert sorted((s[5]["rank"], s[2]) for s in g2h) == sorted(
        (r, s[1]) for s in steps for r in range(2))
    assert all(s[5]["d2h_bytes"] == 4 * n_params for s in g2h)
    opt = _named(recs, "trainer.optimizer")
    assert [s[5]["h2d_bytes"] for s in opt] == [4 * n_params] * 2
    for s in _named(recs, "trainer.loss_and_grad"):
        # 2 rows of 16 + 1 int32 tokens (inputs and their next tokens)
        assert s[5]["h2d_bytes"] == 2 * 17 * 4 and s[5]["d2h_bytes"] == 4
    # the save at the last step snapshots params and both moments
    (save,) = _named(recs, "ckpt.save")
    assert save[2] == steps[-1][1]
    assert save[5]["d2h_bytes"] >= 3 * 4 * n_params
    waits = _named(recs, "jccl.wait_all")
    assert waits and all(w[5]["events"] > 0 for w in waits)
    assert {"trainer.step", "trainer.allreduce", "jccl.wait_all"} <= \
        tracing.stack_names()
    per_step = (tracing.summed("trainer.step", "d2h_bytes", lo, hi)
                - tracing.summed("ckpt.save", "d2h_bytes", lo, hi)) / 2
    assert per_step == 2 * (4 * n_params + 4)


def test_scheduler_over_tp_engine_records_kv_rows_and_queue():
    cfg = gpt2_124m.smoke_config()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, _, world = build_world(n_ranks=2, max_chunk_bytes=1 << 12,
                              fast=True)
    eng = TPServeEngine(model, params, world=world, max_len=24)
    sched = RequestScheduler(eng, n_slots=2, prefill_len=8)
    rng = np.random.RandomState(0)
    lo = time.perf_counter()
    reqs = [sched.submit(rng.randint(1, cfg.vocab, size=4 + i), 3)
            for i in range(3)]
    sched.run()
    hi = time.perf_counter()
    recs = tracing.records(lo, hi)
    # only the step's new K and V rows come to the host, one per slot
    L, n_slots, _, KVh, hd = eng._cache["k"].shape
    kv = 2 * L * n_slots * KVh * hd * eng._cache["k"].dtype.itemsize
    rows = _named(recs, "tp.kv_rows")
    assert rows and all(r[5]["d2h_bytes"] == kv for r in rows)
    ticks = {r[1] for r in _named(recs, "sched.tick")}
    assert len(ticks) == sched.decode_steps
    queued = _named(recs, "sched.queued")
    assert sorted(q[5]["rid"] for q in queued) == [r.rid for r in reqs]
    assert all(q[4] is not None and q[4] >= q[3] for q in queued)
    admits = _named(recs, "tp.admit")
    assert sorted(a[5]["rid"] for a in admits) == [r.rid for r in reqs]
    assert tracing.summed("sched.tick", "d2h_bytes", lo, hi) >= \
        len(rows) * kv
