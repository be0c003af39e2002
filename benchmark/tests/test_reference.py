"""reference.py against the program's ``BERTModel`` at a tiny size on the
CPU, causal and not, with the benchmark's weights in both; and the
training reference (loss, gradients, adam, the dropout masks) against the
program's compiled train step."""
import numpy as np
import pytest

from conftest import TINY, TINY_GEN, TINY_TRAIN_MIX

from benchmark import reference, weights
from benchmark.drivers import bert_program, train


def program_logits(cfg, seed, tokens):
    import mxtpu as mx
    from mxtpu import autograd, nd
    mx.random.seed(0)
    net = bert_program.build_net(dict(cfg, hidden_dropout_prob=0.0))
    net.initialize(init="xavier")
    with autograd.pause():
        net(nd.array(tokens.astype(np.float32)))      # deferred shapes
    pmap = bert_program.param_map(net, cfg)
    w = weights.make(cfg, seed)
    for (p, _), a in zip(pmap, bert_program.program_arrays(pmap, w)):
        p._data._data = a
    with autograd.pause():
        return net(nd.array(tokens.astype(np.float32))).asnumpy(), w


@pytest.mark.parametrize("cfg", [TINY, TINY_GEN], ids=["encoder", "causal"])
def test_forward_agrees_with_the_program(cfg):
    rng = np.random.default_rng(3)
    tokens = rng.integers(1, cfg["vocab_size"], (3, 16))
    got, w = program_logits(cfg, 2 ** 31 + 9, tokens)
    ref = np.asarray(reference.forward(w, tokens, cfg))
    assert got.shape == ref.shape == (3, 16, cfg["vocab_size"])
    assert np.abs(got - ref).max() < 2e-5 * max(1.0, np.abs(ref).max())


def test_causal_reference_does_not_look_ahead():
    rng = np.random.default_rng(4)
    w = weights.make(TINY_GEN, 1)
    a = rng.integers(1, 97, (1, 16))
    b = a.copy()
    b[0, 10:] = rng.integers(1, 97, 6)
    la = np.asarray(reference.forward(w, a, TINY_GEN))
    lb = np.asarray(reference.forward(w, b, TINY_GEN))
    assert np.allclose(la[0, :10], lb[0, :10], atol=1e-6)
    assert not np.allclose(la[0, 10:], lb[0, 10:], atol=1e-3)
    # and the encoder does
    we = weights.make(TINY, 1)
    ea = np.asarray(reference.forward(we, a, TINY))
    eb = np.asarray(reference.forward(we, b, TINY))
    assert not np.allclose(ea[0, :10], eb[0, :10], atol=1e-3)


def test_training_reference_follows_the_compiled_step():
    """Dropout on: the reference draws the program's masks from the
    program's keys, so three adam steps agree to float32 rounding."""
    seed = 2 ** 31 + 5
    prog = train.Program(TINY, TINY_TRAIN_MIX, seed)
    got = train.checked_steps(prog, seed, 3)
    ref = train.reference_run(TINY, TINY_TRAIN_MIX, seed, 3)
    assert np.allclose(got["losses"], ref["losses"], rtol=1e-5)
    checks = train.compare(got, ref)
    assert checks["loss_rel_gap"][0] < 1e-5
    assert checks["grad_norm_gap"][0] < 1e-4
    assert checks["change_norm_gap"][0] < 1e-3
    # a key's bias has no gradient under softmax: the rule on the
    # reference's gradient leaves it (and the unused type embedding) out
    moved = set(train.moved_leaves(ref))
    assert "l0.k_b" not in moved and "type_embed" not in moved
    assert "l0.q_b" in moved and "l1.ffn2_w" in moved
    # without the masks the reference does not follow: dropout is compared
    off = train.reference_run(dict(TINY, hidden_dropout_prob=0.0),
                              TINY_TRAIN_MIX, seed, 3)
    assert train.compare(got, off)["grad_norm_gap"][0] > 1e-2


def test_token_gaps_reads_the_served_positions():
    cfg = TINY_GEN
    w = weights.make(cfg, 7)
    prompt = [5, 9, 11, 3]
    # serve greedily from the reference itself: every gap is nought
    seq = list(prompt)
    for _ in range(6):
        logits = np.asarray(reference.forward(
            w, np.asarray([seq]), cfg))[0, -1]
        seq.append(int(np.argmax(logits)))
    served = seq[len(prompt):]
    (gaps,) = reference.token_gaps(cfg, w, [(prompt, served)])
    assert gaps.shape == (6,) and float(gaps.max()) < 1e-5
    wrong = list(served)
    wrong[2] = (wrong[2] + 1) % cfg["vocab_size"]
    (gaps,) = reference.token_gaps(cfg, w, [(prompt, wrong)])
    assert gaps[2] > 1e-3 and float(gaps[:2].max()) < 1e-5
