"""Operation and byte counts at the two configurations' shapes, against
arithmetic written out by hand."""

import pytest

import cell
import counts

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(params=["phi-moe-2l", "mixtral-8x7b-1l"])
def cfg(request):
    return cell.load_config(request.param)


def test_expert_flops(cfg):
    f = cfg["model"]["moe"]["d_ff_expert"]
    # wi (4096 x 2F) and wo (F x 4096), 2 operations per multiply-add
    assert counts.expert_flops(cfg["model"]) == 2 * 4096 * 2 * f + 2 * f * 4096
    assert counts.expert_flops(cfg["model"]) == {6400: 157_286_400,
                                                 14336: 352_321_536}[f]


def test_token_flops_decode_mfu_terms(cfg):
    m = cfg["model"]
    layers, e = m["num_layers"], m["moe"]["num_experts"]
    proj = 2 * 4096 * (32 * 128 + 2 * 8 * 128) + 2 * 32 * 128 * 4096
    attn = 4 * 32 * 128 * 1000
    router = 2 * 4096 * e
    experts = 2 * counts.expert_flops(m)
    head = 2 * 4096 * m["vocab_size"]
    want = layers * (proj + attn + router + experts) + head
    assert counts.token_flops(m, 1000) == want
    assert counts.token_flops(m, 1000, lm_head=False) == want - head


def test_prompt_flops_sum_of_tokens(cfg):
    m = cfg["model"]
    # a prompt in two chunks costs what its tokens cost one at a time, with
    # the lm_head once, at the last position
    whole = sum(counts.token_flops(m, i + 1, lm_head=False) for i in range(300))
    whole += 2 * 4096 * m["vocab_size"]
    assert (counts.prompt_flops(m, 300, 0, 128)
            + counts.prompt_flops(m, 300, 128, 300)) == whole


def test_lo_pair_cost(cfg):
    m, eng = cfg["model"], cfg["engine"]
    f = m["moe"]["d_ff_expert"]
    ops, nbytes = counts.lo_pair_cost(m, eng, pairs=3, rows=2)
    assert ops == 3 * counts.expert_flops(m)
    codes = (4096 * 2 * f + f * 4096) // 2               # int4: half a byte
    scales = (4096 // 64 * 2 * f + f // 64 * 4096) * 4  # float32 per group
    acts = 3 * (4096 + 2 * f + f) * 4 + 2 * 4096 * 4
    assert nbytes == 3 * (codes + scales) + acts
    # three pairs of int4 weights are bound by memory, not compute
    assert counts.least_time(ops, nbytes, PEAK) == nbytes / PEAK["hbm_bytes_per_s"]


def test_paged_attn_cost(cfg):
    m = cfg["model"]
    ops, nbytes = counts.paged_attn_cost(m, [100, 300])
    assert ops == 4 * 32 * 128 * 400
    assert nbytes == 400 * 8 * 128 * 2 * 2 + 2 * 32 * 128 * 6
