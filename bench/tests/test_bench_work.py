"""The work functions against counts made by hand at small shapes."""
import pytest

from bench.measure import work

CONF = {"hidden_size": 16, "intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 10,
        "amber": {"n": 8, "m": 16, "qgate_skip_layers": [0]}}


def test_nm_prune_matmul_credits_n_over_m():
    flops, nbytes = work("nm_prune_matmul")(4, 16, 8, 8, 16)
    assert flops == 2 * 4 * 16 * 8 / 2          # half the channels kept
    assert nbytes == (4 * 16 + 16 * 8 + 4 * 8) * 2   # x, w, y once each
    dense, _ = work("nm_prune_matmul")(4, 16, 8, 16, 16)
    assert dense == 2 * flops


def test_paged_attention_counts_visible_scores():
    # two queries at positions 3 and 4 see 4 + 5 keys
    flops, nbytes = work("paged_attention")(2, 3, 4, 2, 8, 4)
    assert flops == 4 * 8 * 4 * 9
    # q and o: 2 rows x 4 heads; K and V: 2 blocks of 4 rows x 2 KV heads
    assert nbytes == (2 * 2 * 4 + 2 * 8 * 2) * 8 * 2


@pytest.mark.parametrize("heads_q", [2, 4, 8])
def test_paged_attention_reads_each_kv_head_once(heads_q):
    """More query heads per KV head add q/o bytes only."""
    _, nbytes = work("paged_attention")(1, 31, heads_q, 2, 8, 16)
    assert nbytes == (2 * heads_q + 2 * 32 * 2) * 8 * 2


def test_paged_attention_decode_row():
    flops, _ = work("paged_attention")(1, 99, 1, 1, 2, 16)
    assert flops == 4 * 2 * 100                 # kv_len = offset + 1


def test_model_step_by_hand():
    fn = work("model_step")
    # prefill row: layer 0 skips q/gate (dense), layer 1 prunes them; down
    # is pruned in both
    l0 = 2 * 16 * 16 + 2 * 2 * 16 * 8 + 2 * 16 * 16 + 2 * 16 * 32 * 2 \
        + 2 * 32 * 16 / 2
    l1 = 2 * 16 * 16 / 2 + 2 * 2 * 16 * 8 + 2 * 16 * 16 + 2 * 16 * 32 / 2 \
        + 2 * 16 * 32 + 2 * 32 * 16 / 2
    attn = 2 * 4 * 4 * 4 * 3                    # 2 layers, 3 visible scores
    head = 2 * 16 * 10
    assert fn(CONF, [("prefill", 2, 0, True)]) == 2 * (l0 + l1) + attn + head
    assert fn(CONF, [("prefill", 2, 0, False)]) == 2 * (l0 + l1) + attn
    dense = 2 * (2 * 16 * 16 + 2 * 2 * 16 * 8 + 2 * 16 * 16
                 + 3 * 2 * 16 * 32)
    assert fn(CONF, [("decode", 1, 2)]) == dense + attn + head
