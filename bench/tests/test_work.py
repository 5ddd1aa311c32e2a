"""bench/work.py against a count by hand for a toy triple over two steps.

    python3 -m pytest bench/tests/test_work.py
"""
from bench import work

# d 2, one layer, one head of size 2, one kv head, ff 3, vocabulary 5
TOY = {"arch": "dense", "hidden_size": 2, "num_hidden_layers": 1,
       "num_attention_heads": 1, "num_key_value_heads": 1, "head_dim": 2,
       "intermediate_size": 3, "vocab_size": 5}
# per token: q, k, v, o projections 4 x 2 x 2 = 16, SwiGLU 3 x 2 x 3 = 18,
# so 34 matmul parameters in the layer, 10 in the LM head; attention
# costs 4 x 1 x 2 = 8 FLOPs per key; one key's K/V is 2 x 1 x 2 x 2 = 8
# bytes.
BODY, HEAD, PER_KEY, KV = 34, 10, 8, 8


def costs():
    c = work.Cost.of(TOY)
    return {"draft": c, "target": c, "prm": c}


def test_cost_of_toy_model():
    c = work.Cost.of(TOY)
    assert (c.body, c.head, c.attn, c.kv) == (BODY, HEAD, PER_KEY, KV)


def test_accepted_step():
    # context 3 (positions 0..2, the pending token at 2), a step of 2
    # tokens, 2 candidates.
    w = work.step(costs(), n=2, context=3, length=2, accepted=True)
    # draft and target each: the pending token (position 2, 3 keys) and
    # each candidate's first token (position 3, 4 keys), with logits:
    #   matmul 2 x (34 + 10) x 3 tokens = 264, attention 8 x (3 + 4 + 4)
    #   = 88; K/V read: the 3 shared keys once, 1 own key per candidate.
    # PRM: the pending token and both candidates' 2 tokens (positions 3
    # and 4), no logits:
    #   matmul 2 x 34 x 5 = 340, attention 8 x (3 + 2 x (4 + 5)) = 168;
    #   K/V: 3 shared keys, 2 own keys per candidate.
    flops = 2 * (264 + 88) + (340 + 168)
    attn = 2 * 88 + 168
    kv = 2 * KV * (3 + 2) + KV * (3 + 4)
    assert (w.flops, w.attn_flops, w.attn_bytes) == (flops, attn, kv)


def test_rejected_step_adds_target_candidates_and_their_scoring():
    acc = work.step(costs(), n=2, context=3, length=2, accepted=True)
    rej = work.step(costs(), n=2, context=3, length=2, accepted=False)
    # target candidates: position 3 for each of 2, with logits:
    #   2 x 44 x 2 = 176, attention 8 x (4 + 4) = 64, K/V 3 + 2 keys;
    # their PRM scoring: positions 3 and 4 for each of 2, no logits:
    #   2 x 34 x 4 = 272, attention 8 x 2 x (4 + 5) = 144, K/V 3 + 4 keys.
    assert rej.flops - acc.flops == (176 + 64) + (272 + 144)
    assert rej.attn_flops - acc.attn_flops == 64 + 144
    assert rej.attn_bytes - acc.attn_bytes == KV * (5 + 7)


def test_shared_context_is_read_once_for_all_candidates():
    two = work.step(costs(), n=2, context=40, length=2, accepted=True)
    four = work.step(costs(), n=4, context=40, length=2, accepted=True)
    # doubling the candidates adds only their own keys (1 per draft and
    # target candidate, 2 per PRM candidate), not the 40 shared ones
    assert four.attn_bytes - two.attn_bytes == KV * 2 * (1 + 1 + 2)


def test_prefill_counts_the_unmatched_tail_once_per_model():
    # a 6-token prompt with 4 tokens from the prefix cache: position 4 is
    # fed (position 5 is the pending token, fed by the first step)
    w = work.prefill(costs(), prompt_len=6, hit=4)
    assert w.flops == 3 * (2 * BODY + PER_KEY * 5)
    assert w.attn_bytes == 3 * KV * 5
    assert work.prefill(costs(), prompt_len=6, hit=5).flops == 0


def test_two_steps_sum():
    total = work.Work()
    total.add(work.step(costs(), n=2, context=3, length=2, accepted=True))
    total.add(work.step(costs(), n=2, context=5, length=2, accepted=False))
    a = work.step(costs(), n=2, context=3, length=2, accepted=True)
    b = work.step(costs(), n=2, context=5, length=2, accepted=False)
    assert total.flops == a.flops + b.flops
    assert total.attn_bytes == a.attn_bytes + b.attn_bytes
