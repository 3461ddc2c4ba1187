"""Qwen3-30B-A3B against its plain reference (``bench/references/
qwen3_moe.py``) at smoke size on the CPU.

The program is served through ``repro.serve.api`` with the kernels on
(interpreted here): chunked prefill over the paged cache, then paged
decode, one request at a time on one slot.  Each served token's logits
are read where the step program samples them and compared with the
reference's full forward pass over the prompt and the served tokens.

Tolerance: in float32 (``torch_dtype`` float32, so neither side rounds to
bfloat16) the two compute the same mathematics in a different order
(k-blocked accumulation, chunked attention), so the logits agree to about
1e-5 of their spread; ``LOGIT_TOL`` (1e-3 of the reference logits'
standard deviation) leaves two orders of magnitude of room.  Dropping the
per-head q/k norm or the experts' N:M pruning, or computing in int8 (the
reference's control), moves them by a tenth of the spread or more, and
fails.
"""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from bench import run, system
from bench.measure import Run, reader, work
from bench.tests import smoke
from bench.tests.test_bench_sizes import qwen3_moe

SEED = 2 ** 31 + 1234
LOGIT_TOL = 1e-3
ENGINE = {"slots": 1, "max_seq": 128, "chunk": 16, "block_size": 8}
PROMPTS = (37, 20)          # three chunks (the last padded), and two
NEW = 4


def conf(dtype="float32") -> dict:
    """A Qwen3-shaped smoke file with a q/gate skip layer (layer 1)."""
    return qwen3_moe(reference="qwen3_moe", torch_dtype=dtype)


@pytest.fixture(scope="module")
def f32():
    from bench.references import qwen3_moe
    c = conf()
    return c, qwen3_moe.Reference(c, SEED)


def _prompts(vocab):
    rng = np.random.default_rng(7)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in PROMPTS]


def _served(c, monkeypatch, cfg_over=None):
    """Per prompt: (prompt, served tokens, the logits each token was
    sampled from)."""
    from repro.models import build_model
    from repro.serve.executor import Executor
    seen = []
    sample = Executor._sample

    def recording(self, logits, key):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits)
        return sample(self, logits, key)

    monkeypatch.setattr(Executor, "_sample", recording)
    model, params, pol = system.build(c, SEED)
    if cfg_over:
        model = build_model(dataclasses.replace(model.cfg, **cfg_over))
    eng = system.engine(model, pol, ENGINE)
    out = []
    for prompt in _prompts(c["vocab_size"]):
        seen.clear()
        rid = eng.submit(prompt, max_new_tokens=NEW)
        tokens = list(eng.run(params)["outputs"][rid])
        jax.effects_barrier()
        first = [x for x in seen if x.ndim == 1][-1]     # the final chunk's
        rest = [x[0] for x in seen if x.ndim == 2]       # the one slot's
        out.append((prompt, tokens, np.stack([first] + rest)))
    return out


def _worst(ref, served, control=False) -> float:
    """The largest logit difference over every served token, in units of
    the reference logits' standard deviation."""
    worst = 0.0
    for prompt, tokens, got in served:
        seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
        p = len(prompt)
        pos = np.arange(p - 1, p - 1 + len(tokens))
        want = np.asarray(ref.logits(seq, p, pos))
        if control:
            got = np.asarray(ref.logits(seq, p, pos, control=True))
        assert got.shape == want.shape
        worst = max(worst, float(np.abs(got - want).max() / want.std()))
    return worst


def _program_router(c, monkeypatch, prompt):
    """Per layer the router logits of the prompt's one prefill chunk, as
    the served program computes them."""
    import jax.numpy as jnp

    from repro.models import moe
    seen = {}
    local = moe._moe_local

    def recording(x, p, *args, **kw):
        lg = jnp.dot(x.reshape(-1, x.shape[-1]).astype(jnp.float32),
                     p["router"]["w"], precision=jax.lax.Precision.HIGHEST)
        jax.debug.callback(lambda i, g: seen.setdefault(int(i), np.asarray(
            g)[:len(prompt)]), p["layer"], lg)
        return local(x, p, *args, **kw)

    monkeypatch.setattr(moe, "_moe_local", recording)
    model, params, pol = system.build(c, SEED)
    eng = system.engine(model, pol, dict(ENGINE, chunk=64))
    eng.submit(prompt, max_new_tokens=1)
    eng.run(params)
    jax.effects_barrier()
    return [seen[i] for i in range(c["num_hidden_layers"])]


def _reference_router(ref, prompt):
    """Per layer the reference's router logits over ``prompt``."""
    import jax.numpy as jnp

    from bench.references.qwen2 import ROW_BLOCK
    from bench.references.qwen3_moe import E, P
    w, t = ref.w, len(prompt)
    ids = jnp.asarray(np.pad(prompt, (0, ROW_BLOCK - t)))
    out = []
    with jax.default_matmul_precision("highest"):
        h = w["embed/w"][ids].astype(jnp.float32)
        for i in range(ref.conf["num_hidden_layers"]):
            s = ref.scales.get(i)
            scale = jnp.ones((ref.conf["hidden_size"],)) if s is None else s
            flag = jnp.asarray(s is not None)
            h, x2 = ref._attn[False](h, ref._layer_args(i), scale, flag,
                                     jnp.int32(t))
            r = w[P + "moe/router/w"][i]
            out.append(np.asarray(jnp.dot(x2, r))[:t])
            ids_, gates = ref._route[False](x2, r)
            h = ref._experts[False](
                h, x2, ids_, gates, w[E + "gate_proj/w"][i],
                w[E + "up_proj/w"][i], w[E + "down_proj/w"][i],
                jnp.int32(t), flag)
    return out


@pytest.mark.parametrize("exact", [True, False])
def test_bf16_router_inputs_match_the_reference(monkeypatch, exact):
    """In bfloat16 the served program rounds each value where the
    reference stores one (``executor.EXACT_STORES``; RoPE and the gated
    product computed in float32 and stored once), so every layer's router
    sees the reference's inputs: the first layer's logits agree to float32
    accumulation order, far below a bfloat16 step.  Carried wider through
    XLA's fused chains, they differ by bfloat16 steps, and a top-k near a
    tie swaps experts.  (A later layer inherits the rare swap or N:M flip
    that accumulation order still causes: attention spreads that token's
    change over the tokens after it.)"""
    from bench.references import qwen3_moe
    from repro.serve import executor
    if not exact:
        monkeypatch.setattr(executor, "EXACT_STORES", {})
    c = conf("bfloat16")
    prompt = _prompts(c["vocab_size"])[0]
    got = _program_router(c, monkeypatch, prompt)
    want = _reference_router(qwen3_moe.Reference(c, SEED), prompt)
    # the first router's logits: the median token's largest error
    err = float(np.median(np.abs(got[0] - want[0]).max(-1)) / want[0].std())
    assert (err < 1e-5) if exact else (err > 1e-3)


def test_prefill_then_paged_decode_matches_reference(f32, monkeypatch):
    c, ref = f32
    served = _served(c, monkeypatch)
    assert [len(t) for _, t, _ in served] == [NEW] * len(PROMPTS)
    assert _worst(ref, served) < LOGIT_TOL


@pytest.mark.parametrize("fault", ["no_qk_norm", "experts_dense", "int8"])
def test_tolerance_fails_without_the_mathematics(f32, monkeypatch, fault):
    from repro.models import moe
    c, ref = f32
    if fault == "int8":
        served = _served(c, monkeypatch)
        assert _worst(ref, served, control=True) > 100 * LOGIT_TOL
        return
    over = None
    if fault == "no_qk_norm":
        over = {"qk_norm": False}
    else:
        monkeypatch.setattr(moe, "_prunes", lambda *a: False)
    assert _worst(ref, _served(c, monkeypatch, over)) > 100 * LOGIT_TOL


def test_harness_cell_is_correct_at_smoke_size():
    """The run's own path on the CPU, bfloat16, the Qwen3 reference."""
    res = smoke.resolved("qwen3_30b_a3b.prefill_pool", smoke.OPEN)
    res["conf"] = conf("bfloat16")
    res["cell"]["config"] = "qwen3_30b_a3b"
    out = smoke.execute(res, SEED, 1.0)
    assert out["correct"] and out["failed"] == 0


def test_the_cell_resolves_with_its_metrics():
    res = run.resolve("qwen3_30b_a3b.prefill_pool")
    assert res["conf"]["reference"] == "qwen3_moe"
    assert res["cell"]["chips"] == 1 and res["cell"]["traffic"] == "prefill_pool"
    assert {m["name"] for m in res["per_layer"]} == {
        "nm_grouped_matmul_roofline.prefill", "mfu.prefill",
        "queue_wait_ms_p50.prefill", "nm_prune_matmul_roofline.prefill",
        "paged_attention_roofline.prefill", "idle_share.prefill"}
    cfg = system.model_config(res["conf"])
    assert type(cfg).__name__ == "Qwen3Config" and cfg.qk_norm
    assert (cfg.q_dim, cfg.n_experts, cfg.top_k, cfg.moe_d_ff) == (
        4096, 128, 8, 768)


def test_grouped_matmul_work_by_hand():
    fn = work("nm_grouped_matmul")
    # 4 tokens x top-2 over 4 experts: at least 2 experts' weights read
    flops, nbytes = fn(4, 16, 8, 4, 2, 8, 16, True)
    assert flops == 2 * 8 * 16 * 8 / 2
    assert nbytes == (8 * (16 + 8) + 2 * 16 * 8) * 2
    assert fn(4, 16, 8, 4, 2, 8, 16, False)[0] == 2 * 8 * 16 * 8
    # however many tokens, the fewest experts a call touches is top_k
    assert fn(512, 16, 8, 4, 2, 8, 16, True)[1] == (
        1024 * (16 + 8) + 2 * 16 * 8) * 2


def _q3_run(kernel_s) -> Run:
    from bench.drive import Iteration
    with open(os.path.join(run.HERE, "traffic", "prefill_pool.json")) as f:
        mix = json.load(f)
    iters = [Iteration(0, 0.0, 0.1, [("prefill", 512, 0, False)]),
             Iteration(1, 0.1, 0.2, [("prefill", 20, 512, True),
                                     ("decode", 1, 900)])]
    return Run(conf=run.resolve("qwen3_30b_a3b.prefill_pool")["conf"],
               mix=mix, peaks=smoke.peaks(), chips=1, setup_s=1.0,
               window=(0, 1), tracks=[], iters=iters,
               trace={"window_s": 0.5, "busy_s": 0.3, "kernel_s": kernel_s},
               trace_span=(0.0, 1.0))


def test_grouped_roofline_reads_the_kernel_by_hand():
    """6 layers x (gate, up, down) per prefill chunk and per decode batch
    (dense), each the larger of its two bounds, over the kernel's device
    time."""
    from bench.work.nm_grouped_matmul import work as fn
    r = _q3_run({"nm_grouped_matmul_pallas": 0.05})
    least = 0.0
    for t, prefill in ((512, True), (20, True), (1, False)):
        for din, dout, pruned in ((2048, 768, True), (2048, 768, False),
                                  (768, 2048, True)):
            least += 6 * r.min_time(*fn(t, din, dout, 128, 8, 8, 16,
                                        pruned and prefill))
    got = reader("nm_grouped_matmul_roofline.prefill")(r)
    assert got == pytest.approx(100 * least / 0.05)
    assert 0 < got < 100
    assert reader("nm_grouped_matmul_roofline.prefill")(_q3_run({})) is None
    assert reader("mfu.prefill")(r) == r.mfu() > 0
