"""The comparison that decides ``correct`` fails what it must.  Each fault
test drives the run's own path at smoke size with the timed path broken
underneath: a token altered where the engine produces it, the KV cache
left unchanged by the step that should write it, and half of a decode
batch left out.  The control test puts the reference computed one
precision step down (int8) in the program's place."""
import numpy as np
import pytest

from bench import check
from bench.tests import smoke

SEED = 2 ** 31 + 99


def _second_best(self, logits, key):
    import jax.numpy as jnp
    top = jnp.argmax(logits, axis=-1)
    masked = jnp.where(jnp.arange(logits.shape[-1]) == top[..., None],
                       -jnp.inf, logits)
    return jnp.argmax(masked, axis=-1).astype(jnp.int32)


def _half_batch(self, logits, key):
    """Every other decode row left out: its logits never computed."""
    import jax.numpy as jnp
    if logits.ndim == 2 and logits.shape[0] > 1:
        rows = jnp.arange(logits.shape[0])[:, None]
        logits = jnp.where(rows % 2 == 1, 0.0, logits)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _scatter_unchanged(k_new, v_new, k_pool, v_pool, *args, **kwargs):
    """The step that writes K/V hands back the cache it was given."""
    return k_pool, v_pool


def _broken(monkeypatch, fault):
    from repro.kernels import paged_attention
    from repro.serve.executor import Executor
    if fault == "altered_token":
        monkeypatch.setattr(Executor, "_sample", _second_best)
    elif fault == "half_batch":
        monkeypatch.setattr(Executor, "_sample", _half_batch)
    elif fault == "state_unchanged":
        monkeypatch.setattr(paged_attention, "paged_kv_scatter_pallas",
                            _scatter_unchanged)


@pytest.mark.parametrize("fault", ["altered_token", "state_unchanged",
                                   "half_batch"])
def test_broken_path_is_not_correct(monkeypatch, fault):
    _broken(monkeypatch, fault)
    res = smoke.resolved("qwen2_7b.prefill_pool", smoke.OPEN)
    out = smoke.execute(res, SEED, 1.5)
    assert not out["correct"]
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in out["checks"].values())


def test_int8_control_is_not_correct():
    """At a size a test can hold, the reference computed in int8 picks
    tokens that the bfloat16 reference ranks below its best, at decode
    positions, by more than the smoke limit on average."""
    conf = smoke.conf()
    conf.update(hidden_size=256, intermediate_size=512, vocab_size=4096,
                num_attention_heads=4, num_key_value_heads=2)
    rng = np.random.default_rng(0)
    served = [(rng.integers(0, 4096, 96, dtype=np.int32),
               list(rng.integers(0, 4096, 64))) for _ in range(2)]
    prog, ctl = check.served_gaps(conf, SEED, served, control=True)
    got = check.numbers(ctl, smoke.LIMITS)
    assert all(got[k] > v for k, v in smoke.LIMITS.items())


def test_numbers_by_hand():
    gaps = [np.array([0.5, 0.0, 0.25]), np.array([0.0, 1.0])]
    got = check.numbers(gaps, check.NUMBERS)
    assert got["max_logit_gap"] == 1.0
    assert got["mean_logit_gap"] == pytest.approx(1.75 / 5)
    assert got["mean_logit_gap_decode"] == pytest.approx(1.25 / 3)
    assert got["rms_logit_gap_decode"] == pytest.approx((1.0625 / 3) ** 0.5)
    one = check.numbers([np.array([0.5])], ["mean_logit_gap_decode"])
    assert one["mean_logit_gap_decode"] == np.inf
    assert check.numbers([], ["max_logit_gap"])["max_logit_gap"] == np.inf


def test_reference_nm_selection_by_hand():
    """Eight of every sixteen kept by |x| * s, ties to the lower channel."""
    import jax.numpy as jnp

    from bench.references.qwen2 import _nm
    x = jnp.asarray([[1.0] * 16 + [float(v) for v in range(16)]])
    s = jnp.ones((32,))
    got = np.asarray(_nm(x, s, 8, 16))[0]
    assert got[:16].tolist() == [1.0] * 8 + [0.0] * 8
    assert got[16:].tolist() == [0.0] * 8 + list(range(8, 16))
    s2 = jnp.asarray([1.0] * 16 + [16.0 - i for i in range(16)])
    got2 = np.asarray(_nm(x, s2, 8, 16))[0][16:]
    # scores i * (16 - i) peak in the middle: channels 4..11 stay
    assert np.nonzero(got2)[0].tolist() == list(range(4, 12))
