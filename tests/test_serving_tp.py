"""Tensor-parallel serving tests: byte-identity with the single-host
engine (the fabric moves bytes, never changes them), the ragged-prompt
regression, the continuous-batching scheduler's state machine, and the
request-level fault campaign (rail kill mid-decode drops and corrupts
nothing; an unmaskable double outage fails loudly)."""

import jax
import numpy as np
import pytest

from repro.collectives import build_world
from repro.configs import gpt2_124m, llama4_maverick
from repro.models import build_model
from repro.scenarios import SCENARIOS, run_scenario
from repro.serving import RequestScheduler, ServeEngine, TPServeEngine

MAX_LEN = 32


@pytest.fixture(scope="module", params=["dense", "moe"])
def setup(request):
    """(model, params, shared local engine, prompts) per family — moe
    exercises the expert all-to-all path, dense the pure-gather path."""
    cfg = (gpt2_124m if request.param == "dense"
           else llama4_maverick).smoke_config()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    local = ServeEngine(model, params, max_len=MAX_LEN)
    rng = np.random.RandomState(0)
    prompts = rng.randint(1, cfg.vocab, size=(2, 8)).astype(np.int32)
    return model, params, local, prompts


def _world(channels=1):
    _, _, world = build_world(n_ranks=2, probe_interval=5e-4,
                              max_chunk_bytes=1 << 12, strict_order=False,
                              fast=True, channels=channels)
    return world


# ---------------------------------------------------------------------------
# byte-identity on a healthy fabric
# ---------------------------------------------------------------------------

def test_tp_generate_byte_identical_greedy_and_sampled(setup):
    model, params, local, prompts = setup
    tp = TPServeEngine(model, params, world=_world(), max_len=MAX_LEN,
                       local=local)
    ref_g = local.generate(prompts, 5, greedy=True)
    ref_s = local.generate(prompts, 5, greedy=False, seed=3)
    assert np.array_equal(tp.generate(prompts, 5, greedy=True), ref_g)
    assert np.array_equal(tp.generate(prompts, 5, greedy=False, seed=3),
                          ref_s)
    assert tp.reconstruction_mismatches == 0
    assert tp.sync_rounds == 2 * (5 + 1)  # one sync per prefill/decode step


def test_tp_sync_overlaps_per_layer_gathers(setup):
    """Every decode step issues the logits gather + one gather per layer
    (+ the MoE dispatch) before waiting: the world must observe them
    live simultaneously or the per-layer overlap claim is vacuous."""
    model, params, local, prompts = setup
    world = _world()
    tp = TPServeEngine(model, params, world=world, max_len=MAX_LEN,
                       local=local)
    tp.generate(prompts, 3, greedy=True)
    floor = 1 + model.cfg.n_layers + (1 if model.cfg.family == "moe" else 0)
    assert world.stats_snapshot()["peak_live_collectives"] >= floor


def test_tp_continuous_batching_matches_local_reference(setup):
    """The scheduler over a fabric world reproduces the world=None
    reference token-for-token (identical admission/decode schedule)."""
    model, params, local, _ = setup
    rng = np.random.RandomState(1)
    plist = [rng.randint(1, model.cfg.vocab,
                         size=int(rng.randint(3, 11))).astype(np.int32)
             for _ in range(4)]

    def drive(world):
        eng = TPServeEngine(model, params, world=world, max_len=MAX_LEN,
                            local=local)
        sched = RequestScheduler(eng, n_slots=2, prefill_len=12)
        for p in plist:
            sched.submit(p, 5)
        sched.run()
        return [list(r.tokens) for r in sched.requests], eng

    ref, _ = drive(None)
    got, eng = drive(_world())
    assert got == ref
    assert eng.reconstruction_mismatches == 0


def _host_kv_bytes(cache, prev_len):
    """The K/V row cut made on the host from the whole cache: the
    reference the device cut must match byte for byte."""
    k = np.asarray(cache["k"])
    v = np.asarray(cache["v"])
    S = k.shape[2]
    pl = np.asarray(prev_len)
    if pl.ndim == 0:
        at = min(int(pl), S - 1)
        rows_k, rows_v = k[:, :, at], v[:, :, at]
    else:
        idx = np.clip(pl.astype(np.int64), 0, S - 1)
        idx = idx[None, :, None, None, None]
        rows_k = np.take_along_axis(k, idx, axis=2)[:, :, 0]
        rows_v = np.take_along_axis(v, idx, axis=2)[:, :, 0]
    return {f"kv{layer}": np.concatenate(
                [np.ascontiguousarray(rows_k[layer]).reshape(-1).view(np.uint8),
                 np.ascontiguousarray(rows_v[layer]).reshape(-1).view(np.uint8)])
            for layer in range(k.shape[0])}


KV_S = 7


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("prev_len", [
    np.int32(3), np.int32(KV_S - 1), np.int32(KV_S + 2),
    np.array([0, 3, 6], np.int32),
    np.array([KV_S - 1, KV_S + 4, 2], np.int32)],
    ids=["scalar", "scalar_last", "scalar_past", "vector",
         "vector_last_and_past"])
def test_step_kv_bytes_match_host_cut(dtype, prev_len):
    """The rows cut on the device are the host cut's bytes, under the
    same keys in the same order: the fabric gathers and verifies the
    same payload as when the whole cache went to the host."""
    model = build_model(gpt2_124m.smoke_config())
    eng = TPServeEngine(model, None, max_len=KV_S)
    L, B, KVh, hd = 2, 3, 2, 4
    kk, kv = jax.random.split(jax.random.PRNGKey(0))
    cache = {"k": jax.random.normal(kk, (L, B, KV_S, KVh, hd), dtype),
             "v": jax.random.normal(kv, (L, B, KV_S, KVh, hd), dtype)}
    got = eng._step_kv_bytes(cache, prev_len)
    want = _host_kv_bytes(cache, prev_len)
    assert list(got) == list(want)
    for name in want:
        assert got[name].dtype == np.uint8
        assert np.array_equal(got[name], want[name])


def test_tp_rejects_cacheless_families():
    cfg = gpt2_124m.smoke_config()
    cfg = cfg.__class__(**{**cfg.__dict__, "family": "rwkv6"})
    model = build_model(cfg)
    with pytest.raises(ValueError, match="dense/audio/moe"):
        TPServeEngine(model, None, max_len=MAX_LEN)


# ---------------------------------------------------------------------------
# ragged-prompt regression (the serving sampling bugfix)
# ---------------------------------------------------------------------------

def test_ragged_prompts_match_unpadded_runs(setup):
    """Right-padded ragged prompts with ``prompt_lens`` must generate
    exactly what each sequence generates alone unpadded — the old code
    sampled every row from the PAD column's logits."""
    model, params, local, _ = setup
    rng = np.random.RandomState(2)
    lens = [3, 5, 8, 6]
    S = max(lens)
    prompts = np.zeros((len(lens), S), np.int32)
    rows = [rng.randint(1, model.cfg.vocab, size=l).astype(np.int32)
            for l in lens]
    for i, row in enumerate(rows):
        prompts[i, :lens[i]] = row
    out = local.generate(prompts, 4, greedy=True,
                         prompt_lens=np.array(lens))
    if model.cfg.family == "dense":
        for i, row in enumerate(rows):
            solo = local.generate(row[None, :], 4, greedy=True)
            assert np.array_equal(out[i, S:], solo[0, lens[i]:]), \
                f"row {i} (len {lens[i]}) diverged from its unpadded run"
    else:
        # MoE expert-capacity contention couples rows within a batch
        # (a row's token can be dropped because ANOTHER row routed to
        # the same expert), so solo equivalence is defined only for
        # dense models; the ragged path must still be schedule-
        # deterministic — identical calls, identical bytes.
        out2 = local.generate(prompts, 4, greedy=True,
                              prompt_lens=np.array(lens))
        assert np.array_equal(out, out2)


def test_generate_overflow_and_bad_lens_raise_valueerror(setup):
    model, params, local, prompts = setup
    with pytest.raises(ValueError, match="exceed"):
        local.generate(prompts, MAX_LEN, greedy=True)
    with pytest.raises(ValueError, match="shape"):
        local.generate(prompts, 2, prompt_lens=np.array([3]))
    with pytest.raises(ValueError, match=r"\[1, S\]"):
        local.generate(prompts, 2, prompt_lens=np.array([0, 9]))


# ---------------------------------------------------------------------------
# scheduler state machine
# ---------------------------------------------------------------------------

def test_scheduler_state_machine_and_token_counts(setup):
    model, params, local, _ = setup
    eng = TPServeEngine(model, params, world=None, max_len=MAX_LEN,
                        local=local)
    sched = RequestScheduler(eng, n_slots=2, prefill_len=10)
    rng = np.random.RandomState(3)
    reqs = [sched.submit(rng.randint(1, model.cfg.vocab, size=4), n)
            for n in (1, 3, 6, 2)]
    assert [r.state for r in reqs] == ["queued"] * 4
    sched.run()
    assert [r.state for r in reqs] == ["done"] * 4
    assert [len(r.tokens) for r in reqs] == [1, 3, 6, 2]
    assert not sched.pending and sched.queue == type(sched.queue)()
    assert all(s is None for s in sched.slots)


def test_scheduler_fail_outstanding_marks_queued_and_active(setup):
    model, params, local, _ = setup
    eng = TPServeEngine(model, params, world=None, max_len=MAX_LEN,
                        local=local)
    sched = RequestScheduler(eng, n_slots=1, prefill_len=10)
    rng = np.random.RandomState(4)
    reqs = [sched.submit(rng.randint(1, model.cfg.vocab, size=4), 8)
            for _ in range(3)]
    sched.step()                       # request 0 active, 1-2 queued
    assert reqs[0].state == "active"
    assert sched.fail_outstanding() == 3
    assert [r.state for r in reqs] == ["failed"] * 3
    assert not sched.pending


def test_scheduler_rejects_bad_requests(setup):
    model, params, local, _ = setup
    eng = TPServeEngine(model, params, world=None, max_len=MAX_LEN,
                        local=local)
    sched = RequestScheduler(eng, n_slots=1, prefill_len=8)
    with pytest.raises(ValueError):
        sched.submit(np.array([1, 2], np.int32), 0)     # n_tokens < 1
    sched.submit(np.arange(1, 12, dtype=np.int32), 2)   # prompt > prefill_len
    with pytest.raises(ValueError, match="outside"):
        sched.step()


# ---------------------------------------------------------------------------
# the serving fault campaign (request-level invariants)
# ---------------------------------------------------------------------------

SERVING_SCENARIOS = ["baseline_clean", "sender_nic_down",
                     "nic_down_permanent", "link_flap_train",
                     "rail_kill_striped"]


@pytest.mark.parametrize("name", SERVING_SCENARIOS)
def test_serving_campaign_masks_faults_without_request_loss(name):
    sc = SCENARIOS[name]
    r = run_scenario(sc, workload="serving")
    assert r.ok, r.violations
    assert r.completed and not r.aborted
    assert r.requests_failed == 0 and r.token_mismatches == 0
    assert r.payload_mismatches == 0
    assert r.fallbacks >= sc.min_fallbacks
    if name == "rail_kill_striped":     # rail kill mid-decode, striped
        assert r.resteered_chunks >= 1


def test_serving_unmaskable_fails_requests_loudly():
    r = run_scenario(SCENARIOS["double_rail_outage"], workload="serving")
    assert r.ok, r.violations
    assert r.aborted and r.requests_failed >= 1
    assert r.token_mismatches == 0      # completed requests stayed correct


def test_serving_campaign_deterministic():
    r1 = run_scenario(SCENARIOS["link_flap_train"], workload="serving",
                      seed=7)
    r2 = run_scenario(SCENARIOS["link_flap_train"], workload="serving",
                      seed=7)
    assert r1.fingerprint() == r2.fingerprint()
