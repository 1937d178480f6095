"""Step-3 (``Step3TextForCausalLM``) at a tiny size on the CPU: the
program's expert layer and low-rank-query attention against the float32
reference of ``bench/archs/Step3TextForCausalLM.py``, the TP server's
continuous batching against the reference's full forward pass, the held
shares of an expert layer against the whole layer, a tiny cell through
the harness, and the counts at the published widths written out by
hand."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import arch, leaf_paths
from bench.tests.bench_cells import add_cpu_peaks, run_cell
from bench.tests.tiny_root import BENCH, ROOT, TINY_LIMITS, make_root

CELL = "serve.step3.reasoning"


def tiny_step3(dtype="bfloat16", first=4, held=4) -> dict:
    """The published configuration at a tiny size: 1 dense and 2 MoE
    layers, 12 router experts of which ``held`` from ``first`` here."""
    cfg = json.loads((BENCH / "configs" / "step3.l5v16k.json").read_text())
    cfg.update(name="tiny.step3", hidden_size=64, intermediate_size=96,
               num_hidden_layers=3, dense_layers_held=1, vocab_size=256,
               num_attention_heads=4, head_dim=16, share_q_dim=32,
               moe_intermediate_size=32, share_expert_dim=32,
               moe_router_experts=12, moe_held_first=first,
               moe_num_experts=held, param_dtype=dtype, compute_dtype=dtype)
    return cfg


def _arch(cfg):
    return arch(ROOT, cfg)


def _first_moe(params):
    return jax.tree_util.tree_map(lambda a: a[0], params["blocks"])


def test_program_weights_are_the_reference_weights():
    """The program's initial weights, by its own recipe, are the
    reference's, leaf for leaf; a held range holds the numbers of that
    range of the whole layer."""
    from repro.models import build_model

    cfg = tiny_step3()
    a = _arch(cfg)
    model = build_model(a.model_config(cfg))
    prog = jax.jit(model.init)(jax.random.PRNGKey(3))
    ref = a.init_params(cfg, 3)
    assert leaf_paths(prog) == leaf_paths(ref)
    for p, r in zip(jax.tree_util.tree_leaves(prog),
                    jax.tree_util.tree_leaves(ref)):
        assert p.dtype == r.dtype and np.array_equal(np.asarray(p),
                                                     np.asarray(r))
    whole = _first_moe(a.init_params(tiny_step3(first=0, held=12), 3))
    part = _first_moe(ref)
    for w in ("w_gate", "w_up", "w_down"):
        assert np.array_equal(np.asarray(part["moe"][w]),
                              np.asarray(whole["moe"][w][4:8]))


def test_held_ranges_sum_to_the_whole_layer():
    """Six held ranges of two experts each, the shared expert counted
    once, add up to the reference's uncut expert layer."""
    from repro.models import blocks

    cfg = tiny_step3("float32", first=0, held=12)
    a = _arch(cfg)
    m = _first_moe(a.init_params(cfg, 5))["moe"]
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 10, 64), jnp.float32)
    whole = a.expert_layer(h[0], m, cfg)
    total = 0.0
    for first in range(0, 12, 2):
        mc = a.model_config(dict(cfg, moe_held_first=first,
                                 moe_num_experts=2))
        part = dict(m, **{w: m[w][first:first + 2]
                          for w in ("w_gate", "w_up", "w_down")})
        y, rows = blocks.moe_mlp(h, part, mc)
        total = total + y[0]
        assert rows.shape == (2,)
    shared = blocks.mlp(h, m["shared"], mc)[0]
    np.testing.assert_allclose(np.asarray(total - 5 * shared),
                               np.asarray(whole), rtol=1e-4, atol=1e-5)


def test_low_rank_query_attention_matches_the_reference():
    from repro.models import attention

    cfg = tiny_step3("float32")
    a = _arch(cfg)
    mc = a.model_config(cfg)
    blk = _first_moe(a.init_params(cfg, 7))["attn"]
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 20, 64), jnp.float32)
    pos = jnp.arange(20)[None]
    got, (k, v) = attention.attention_sublayer(h, blk, mc, pos)
    assert k.shape == v.shape == (1, 20, 1, 16)
    want = a.attention(h[0], blk, cfg)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_tp_engine_matches_the_reference():
    """Continuous batching on the TP server, admission then decoding
    through the slot cache, gives at every served position the
    reference's logits over the whole sequence."""
    from repro.models import build_model
    from repro.serving import RequestScheduler, TPServeEngine

    cfg = tiny_step3("float32")
    a = _arch(cfg)
    model = build_model(a.model_config(cfg))
    params = jax.jit(model.init)(jax.random.PRNGKey(9))
    eng = TPServeEngine(model, params, world=None, max_len=40)
    sched = RequestScheduler(eng, n_slots=3, prefill_len=16)
    seen = {}
    sync, admit, decode = eng._sync, eng.admit, eng.decode_batch
    rows = []

    def capture(logits, *args):
        rec = sync(logits, *args)
        rows.append(np.asarray(rec, np.float32)[:, -1])
        return rec

    def admit_(slot, prompt):
        tok = admit(slot, prompt)
        seen.setdefault(sched.slots[slot].rid, []).append(rows[-1][0])
        return tok

    def decode_(feed):
        active = [(s, r) for s, r in enumerate(sched.slots) if r is not None]
        toks = decode(feed)
        for s, r in active:
            seen[r.rid].append(rows[-1][s])
        return toks
    eng._sync, eng.admit, eng.decode_batch = capture, admit_, decode_
    rng = np.random.default_rng(0)
    for n, out in ((5, 8), (16, 6), (9, 10), (12, 5)):
        sched.submit(rng.integers(0, 256, n, dtype=np.int32), out)
    sched.run()
    ref_params = a.init_params(cfg, 9)
    fwd = jax.jit(lambda p, t: a.sequence_logits(p, t, cfg))
    for r in sched.requests:
        full = np.concatenate([r.prompt, np.asarray(r.tokens[:-1])])
        want = np.asarray(fwd(ref_params, jnp.asarray(full)))
        want = want[r.prompt.size - 1:]
        got = np.stack(seen[r.rid][:len(r.tokens)])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-4)
    assert eng._cache["expert_rows"].shape == (2, 4)


# ---------------------------------------------------------------------------
# a tiny cell through the harness
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A test checkout with ``tiny.step3.chat``: the tiny configuration
    under the tiny chat mix, added to every list the real cell is in."""
    r = make_root(tmp_path_factory.mktemp("checkout"))
    add_cpu_peaks(r)
    b = r / "bench"
    (b / "configs" / "tiny.step3.json").write_text(json.dumps(tiny_step3()))
    (b / "limits" / "tiny.step3.chat.json").write_text(
        json.dumps(TINY_LIMITS["serve"]))
    bench = json.loads((r / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny.step3", "source": "tiny",
                             "reduced": [], "why": "tests",
                             "file": "bench/configs/tiny.step3.json"})
    bench["workloads"].append({"name": "tiny.step3.chat",
                               "config": "tiny.step3",
                               "traffic": "tiny_chat_open_loop",
                               "chips": 1, "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny.step3.chat")
    (r / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return r


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_step3_cell_is_correct(root, trace, monkeypatch, capsys):
    rc, line, err = run_cell(root, "tiny.step3.chat", monkeypatch, capsys,
                             trace=trace)
    assert rc == 0 and line["correct"] is True, err
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in bench[kind]
            if CELL in m.get("workloads", [CELL])}
    assert set(line["metrics"]) == want
    if trace:
        assert 0 < line["metrics"]["expert_hit_pct.serve"]["value"] <= 100


# ---------------------------------------------------------------------------
# the counts at the published widths, by hand
# ---------------------------------------------------------------------------

STEP3 = json.loads((BENCH / "configs" / "step3.l5v16k.json").read_text())
# W_qkv 7168 x 2560, query norm 2048, W_q 2048 x 16384, W_o 16384 x 7168
ATTN = 18_350_080 + 2_048 + 33_554_432 + 117_440_512
EXPERT = 3 * 7168 * 5120          # one SwiGLU of 5120
DENSE = 3 * 7168 * 18432          # the dense SwiGLU
ROUTER = 7168 * 48
HEAD = 7168 * 16102


def test_parameter_counts():
    a = _arch(STEP3)
    assert a.attention_params(STEP3) == ATTN == 169_347_072
    assert a.expert_params(STEP3) == EXPERT == 110_100_480
    # a MoE layer here: attention, two norms, router, 8 held experts and
    # the shared one
    moe_layer = ATTN + 2 * 7168 + ROUTER + 9 * EXPERT
    assert moe_layer == 1_160_609_792
    total = 2 * HEAD + (ATTN + 2 * 7168 + DENSE) + 4 * moe_layer + 7168
    assert a.param_count(STEP3) == total == 5_439_007_744
    assert a.model_config(STEP3).param_count() == total


@pytest.mark.parametrize("lens", [[0], [10, 0], [255, 3, 48, 1000],
                                  [3071] * 32])
def test_decode_bytes_by_hand(lens):
    a = _arch(STEP3)
    T = len(lens)
    hit = 8 * (1 - (45 / 48) ** T)             # held experts hit a layer
    weights = 5_439_007_744 - HEAD - 4 * 8 * EXPERT
    assert weights == 1_800_373_248
    kv = 2 * 5 * 1 * 256 * sum(n + 1 for n in lens) * 2
    assert a.decode_bytes(STEP3, lens, 2) == pytest.approx(
        2 * (weights + 4 * hit * EXPERT) + kv, rel=1e-12)


def test_operation_counts_by_hand():
    a = _arch(STEP3)
    # a token multiplies through 5 attention blocks (not the query
    # norm), the dense SwiGLU, and per MoE layer the router, the shared
    # expert and 3 x 8/48 held pairs
    token = 5 * (ATTN - 2048) + DENSE + 4 * (ROUTER + EXPERT + 0.5 * EXPERT)
    assert token == 1_905_065_984
    assert a.decode_flops(STEP3, [10, 0]) == pytest.approx(
        2 * 2 * (token + HEAD) + 4 * 64 * 256 * (11 + 1) * 5, rel=1e-12)
    assert a.prefill_flops(STEP3, 5) == pytest.approx(
        2 * token * 5 + 4 * 64 * 256 * 15 * 5 + 2 * HEAD, rel=1e-12)
    attn = 4 * 64 * 256 * (4096 * 4097 // 2) * 5 / 4096
    assert a.train_flops_per_token(STEP3, 4096) == pytest.approx(
        3 * (2 * (token + HEAD) + attn), rel=1e-12)
