"""The hybrid backbone's ``lfm2_moe`` sublayers (dl/hybrid.py: the gated
short convolution, attention with QK-norm and rotary positions, the dense
SwiGLU, SwiGLU experts selected by ``s + expert_bias``) against their plain
reference (benchmark/references/lfm2_24b_a2b_ft.py) at a small size on the
CPU, seeded weights, float32: every sublayer and the whole stack, forward and
gradient; causality and padding; routing; the chip's share of the experts
against the uncut layer; the estimator.

Tolerances: the program and the reference compute the same float32
arithmetic in another order (fused products, blocks of queries, grouped
products over sorted pairs, a chunked sum where the reference loops), so
they agree to a few units in 1e-5 of the largest value; 2e-4 forward and
5e-4 for a sublayer's gradients, 1e-3 for the stack's, leave that room and
no more: the program in bfloat16 misses the stack's forward by 1e-3 or more
(``test_bfloat16_misses_the_tolerance``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from synapseml_tpu.core import PipelineStage, Table
from synapseml_tpu.dl import hybrid
from synapseml_tpu.dl.text import DeepTextClassifier

CELL = "lfm2_24b_a2b_fit"
FORWARD, BLOCK_GRADIENT, STACK_GRADIENT = 2e-4, 5e-4, 1e-3


@pytest.fixture(scope="module")
def small():
    """(configuration at the rehearsal's sizes, the reference's module, the
    program's architecture, the reference's sizes)."""
    _, _, config, _ = harness.load_cell(CELL, True)
    ref = harness._load_module("references", "lfm2_24b_a2b_ft")
    arch = hybrid.HybridArch.from_source(
        config["estimator"]["architecture"], hidden=config["hidden_size"],
        layers=config["num_hidden_layers"],
        heads=config["num_attention_heads"], vocab=config["vocab_size"])
    return config, ref, arch, ref.Sizes(config)


@pytest.fixture(scope="module")
def stack(small):
    config, ref, arch, _ = small
    params = ref.initial_parameters(config, 7, 2)
    ids = np.zeros((3, 29), np.int32)
    rng = np.random.default_rng(0)
    for i, n in enumerate((29, 17, 5)):
        ids[i, :n] = rng.integers(2, config["vocab_size"], n)
        ids[i, 0] = 1
    return params, jnp.asarray(ids)


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-6), (
        np.abs(a - b).max(), np.abs(b).max())


def _layer_of(params, small, kind):
    i = small[2].pattern.index(kind)
    return {k: v for k, v in params[f"layer_{i}"].items() if k != "norm"}


def _experts_ref(ref, p, x, z, how=None):
    return ref._experts(p, x.reshape(-1, x.shape[-1]), z, lambda v: v,
                        how or ref.switches()).reshape(x.shape)


BLOCKS = {
    "C": (hybrid.short_conv_mixer,
          lambda ref, p, x, z: ref._short_conv(p, x, z, lambda v: v,
                                               ref.switches())),
    "*": (hybrid.attention_mixer,
          lambda ref, p, x, z: ref._attention(p, x, z, lambda v: v,
                                              ref.switches())),
    "F": (lambda p, x, arch: hybrid._expert(x, p["up"], p["down"], True),
          lambda ref, p, x, z: ref._swiglu(x, p["up"], p["down"],
                                           lambda v: v)),
    "E": (lambda p, x, arch: hybrid.experts_mixer(p, x, arch)[0],
          _experts_ref),
}


def test_the_layer_types_expand_to_operator_then_ffn(small):
    _, _, arch, z = small
    assert arch.pattern == "CFCF*ECECECE"
    kinds = {"conv": "C", "attention": "*", "dense": "F", "experts": "E"}
    assert "".join(kinds[k] for k, _ in z.sublayers()) == arch.pattern
    assert (arch.gated_experts, arch.expert_bias, arch.qk_norm,
            arch.shared_width, arch.route_eps) == (True, True, True, 0, 1e-6)
    assert arch.rope_theta == 1e6 and arch.conv == 3 and arch.dense_width == 96


@pytest.mark.parametrize("src, error", [
    ({"layer_types": ["conv"] * 5}, ValueError),
    ({"layer_types": ["conv"] * 5 + ["sliding_attention"]}, ValueError),
    ({"conv_bias": True}, NotImplementedError),
    ({"norm_topk_prob": False}, NotImplementedError),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
     NotImplementedError),
    ({"held_experts": [0, 16]}, ValueError),
])
def test_what_the_program_cannot_run_is_refused(small, src, error):
    config = small[0]
    with pytest.raises(error):
        hybrid.HybridArch.from_source(
            {**config["estimator"]["architecture"], **src},
            hidden=config["hidden_size"], layers=config["num_hidden_layers"],
            heads=config["num_attention_heads"], vocab=config["vocab_size"])


@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_block_forward_and_gradient_match_the_reference(small, stack, kind):
    config, ref, arch, z = small
    p = _layer_of(stack[0], small, kind)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 21, arch.hidden))
    ours, theirs = BLOCKS[kind]
    with jax.default_matmul_precision("highest"):
        _close(ours(p, x, arch), theirs(ref, p, x, z), FORWARD)
        probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
        g_ours = jax.grad(lambda p, x: (ours(p, x, arch) * probe).sum(),
                          (0, 1))(p, x)
        g_ref = jax.grad(lambda p, x: (theirs(ref, p, x, z) * probe).sum(),
                         (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g_ours), jax.tree.leaves(g_ref)):
        _close(a, b, BLOCK_GRADIENT)


def _stack_loss(model, ids, y):
    def loss(p):
        logits = model.apply({"params": p}, ids, train=True)
        return -jnp.take_along_axis(jax.nn.log_softmax(logits), y[:, None],
                                    -1).sum()
    return loss


def test_stack_forward_loss_and_gradient_match_the_reference(small, stack):
    config, ref, arch, _ = small
    params, ids = stack
    model = hybrid.HybridBackbone(arch, num_classes=2)
    y = jnp.asarray([0, 1, 1])
    with jax.default_matmul_precision("highest"):
        _close(jax.jit(lambda p: model.apply({"params": p}, ids))(params),
               jax.jit(lambda p: ref.forward(p, ids, config))(params),
               FORWARD)
        loss_ours, ours = jax.jit(jax.value_and_grad(
            _stack_loss(model, ids, y)))(params)
        loss_ref, theirs = jax.jit(jax.value_and_grad(lambda p: ref.loss_sum(
            p, ids, y, config, "float32", None)))(params)
    _close(loss_ours, loss_ref, FORWARD)
    flat_o, flat_r = ref.flatten(ours), ref.flatten(theirs)
    assert set(flat_o) == set(flat_r)
    for k in flat_r:
        _close(flat_o[k], flat_r[k], STACK_GRADIENT)
    # the selection bias is a buffer: no gradient reaches it
    for k in flat_o:
        if k.endswith("expert_bias"):
            assert not np.asarray(flat_o[k]).any()


def test_bfloat16_misses_the_tolerance(small, stack):
    """The tolerances are tight enough that the program in the next lower
    precision than the reference's fails them."""
    config, ref, arch, _ = small
    params, ids = stack
    model = hybrid.HybridBackbone(arch, num_classes=2, dtype=jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params}, ids), np.float64)
        want = np.asarray(ref.forward(params, ids, config), np.float64)
    assert np.abs(got - want).max() > 5 * FORWARD * np.abs(want).max()


def test_initial_parameters_are_the_references(small):
    from synapseml_tpu.dl.trainer import FlaxTrainer, TrainConfig

    config, ref, arch, _ = small
    model = hybrid.HybridBackbone(arch, num_classes=2)
    ours = FlaxTrainer(model, TrainConfig(seed=11)).init(
        np.zeros((1, 2), np.int32), jit=True).params
    theirs = ref.initial_parameters(config, 11, 2)
    flat_o, flat_r = ref.flatten(ours), ref.flatten(theirs)
    assert set(flat_o) == set(flat_r)
    for k in flat_r:
        np.testing.assert_allclose(np.asarray(flat_o[k]),
                                   np.asarray(flat_r[k]), rtol=2e-6,
                                   atol=1e-9, err_msg=k)
    bias = np.asarray(flat_o["layer_5/expert_bias"])
    assert bias.shape == (arch.experts,) and bias.std() > 0


@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_a_position_changes_nothing_before_it(small, stack, kind):
    _, _, arch, _ = small
    p = _layer_of(stack[0], small, kind)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 21, arch.hidden))
    ours = BLOCKS[kind][0]
    t = 13
    moved = x.at[:, t].add(1.0)
    a, b = np.asarray(ours(p, x, arch)), np.asarray(ours(p, moved, arch))
    assert np.array_equal(a[:, :t], b[:, :t])
    assert not np.allclose(a[:, t:], b[:, t:])


def test_the_short_conv_reads_the_kernel_positions_back(small, stack):
    """Each output sees its own position and the ``conv_L_cache - 1``
    before it, no further."""
    _, _, arch, _ = small
    p = _layer_of(stack[0], small, "C")
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 21, arch.hidden))
    a = np.asarray(hybrid.short_conv_mixer(p, x, arch))
    b = np.asarray(hybrid.short_conv_mixer(p, x.at[:, 9].add(1.0), arch))
    moved = np.abs(a - b).max(-1)[0]
    assert np.flatnonzero(moved).tolist() == list(range(9, 9 + arch.conv))


def test_pad_after_the_last_word_changes_nothing(small, stack):
    _, _, arch, _ = small
    params, ids = stack
    model = hybrid.HybridBackbone(arch, num_classes=2)
    apply = jax.jit(lambda ids: model.apply({"params": params}, ids))
    whole = apply(ids)
    _close(apply(ids[2:, :5])[0], whole[2], 1e-5)      # the row unpadded
    _close(apply(jnp.pad(ids, ((0, 0), (0, 11)))), whole, 1e-5)


def test_selection_reads_s_plus_b_and_the_weights_read_s(small, stack):
    _, _, arch, _ = small
    p = _layer_of(stack[0], small, "E")
    x = jax.random.normal(jax.random.PRNGKey(5), (64, arch.hidden))
    bias = jnp.zeros(arch.experts).at[6].set(10.0).at[2].set(-10.0)
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.sigmoid(x @ p["router"]))
        ids, w = (np.asarray(v) for v in hybrid.route(x, p["router"], arch,
                                                      bias))
        plain, _ = hybrid.route(x, p["router"], arch)
    assert (ids == 6).any(-1).all() and not (ids == 2).any()
    want = np.sort(np.argsort(-(s + np.asarray(bias)), -1)[:, :arch.top_k])
    assert np.array_equal(np.sort(ids, -1), want)
    chosen = np.take_along_axis(s, ids, -1)
    np.testing.assert_allclose(
        w, chosen / (chosen.sum(-1, keepdims=True) + 1e-6) * arch.scaling,
        rtol=1e-6)
    # without the bias, other experts: the two read different numbers
    assert not np.array_equal(np.sort(np.asarray(plain), -1), want)


def test_the_measured_share_of_choices_the_seeded_bias_moves(small, stack):
    """The rehearsal's ``expert_bias_std`` moves a share of the choices of
    the same order as the cell's does at the published widths."""
    _, _, arch, _ = small
    p = _layer_of(stack[0], small, "E")
    x = jax.random.normal(jax.random.PRNGKey(6), (2048, arch.hidden))
    y = hybrid.rms_norm(x, jnp.ones(arch.hidden), arch.eps)
    with_bias, _ = hybrid.route(y, p["router"], arch, p["expert_bias"])
    without, _ = hybrid.route(y, p["router"], arch)
    a, b = np.sort(np.asarray(with_bias), -1), np.sort(np.asarray(without),
                                                       -1)
    moved = np.mean([len(set(r) - set(q)) for r, q in zip(a, b)]) / arch.top_k
    assert 0.05 < moved < 0.5, moved


def test_no_pair_is_dropped_when_one_expert_takes_every_token(small, stack,
                                                              monkeypatch):
    _, _, arch, _ = small
    # segments of 16 rows: the one expert's pairs fill seven of them
    monkeypatch.setattr(hybrid, "segment_rows", lambda *_: 16)
    p = dict(_layer_of(stack[0], small, "E"))
    # a bias that sends every token to the held expert 2
    p["expert_bias"] = jnp.zeros(arch.experts).at[2].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 37, arch.hidden))
    with jax.default_matmul_precision("highest"):
        flat = x.reshape(-1, arch.hidden)
        ids, w = hybrid.route(flat, p["router"], arch, p["expert_bias"])
        routed, counts = hybrid.held_experts_part(
            flat, ids, w, p["experts_up"], p["experts_down"], arch.held,
            arch.experts)
        assert int(counts[2]) == flat.shape[0]
        assert int(counts.sum()) == int(np.isin(np.asarray(ids),
                                                arch.held).sum())
        want = sum(
            jnp.where(ids == e, w, 0.0).sum(-1, keepdims=True)
            * hybrid._expert(flat, p["experts_up"][j], p["experts_down"][j],
                             True)
            for j, e in enumerate(arch.held))
        _close(routed, want, 1e-4)
        out, _ = hybrid.experts_mixer(p, x, arch)
    _close(out.reshape(flat.shape), want, 1e-4)


@pytest.mark.parametrize("pairs, held, experts, rows", [
    (16384 * 4, 8, 64, 16384),      # the cell's segment: two even loads
    (16384 * 4, 64, 64, 16384 * 4),  # every expert held: one segment
    (333, 4, 16, 168),              # 166.5 pairs, to eight rows
    (96, 16, 16, 96),               # never more rows than pairs
])
def test_a_segment_holds_two_even_loads(pairs, held, experts, rows):
    assert hybrid.segment_rows(pairs, held, experts) == rows


@pytest.mark.parametrize("rows", [16, 40, 96])
def test_the_segments_size_changes_no_value(small, stack, monkeypatch,
                                            rows):
    """However many rows a segment takes, and however many of them past the
    last pair are in no group, the held experts' part is the plain sum over
    the chosen pairs, and every pair is counted."""
    _, _, arch, _ = small
    monkeypatch.setattr(hybrid, "segment_rows", lambda *_: rows)
    p = _layer_of(stack[0], small, "E")
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 45, arch.hidden))
    with jax.default_matmul_precision("highest"):
        flat = x.reshape(-1, arch.hidden)
        ids, w = hybrid.route(flat, p["router"], arch, p["expert_bias"])
        routed, counts = hybrid.held_experts_part(
            flat, ids, w, p["experts_up"], p["experts_down"], arch.held,
            arch.experts)
        want = sum(
            jnp.where(ids == e, w, 0.0).sum(-1, keepdims=True)
            * hybrid._expert(flat, p["experts_up"][j], p["experts_down"][j],
                             True)
            for j, e in enumerate(arch.held))
    assert int(counts.sum()) == int(np.isin(np.asarray(ids), arch.held).sum())
    _close(routed, want, 1e-4)


def test_the_eight_shares_add_up_to_the_uncut_layer(small, stack):
    """A layer of 64 experts cut as the cell cuts it, 8 a chip: the routed
    parts of the eight shares, with no shared expert to count once, are the
    layer with every expert held (guide section 4)."""
    _, _, arch, _ = small
    key = jax.random.PRNGKey(8)
    n, per = 64, 8
    whole = dataclasses.replace(arch, experts=n, held=tuple(range(n)))
    up_width = 2 * arch.expert_width
    p = {"router": 0.3 * jax.random.normal(key, (arch.hidden, n)),
         "expert_bias": 0.05 * jax.random.normal(jax.random.fold_in(key, 1),
                                                 (n,)),
         "experts_up": 0.1 * jax.random.normal(
             jax.random.fold_in(key, 2), (n, arch.hidden, up_width)),
         "experts_down": 0.1 * jax.random.normal(
             jax.random.fold_in(key, 3), (n, arch.expert_width, arch.hidden))}
    x = jax.random.normal(jax.random.fold_in(key, 4), (2, 23, arch.hidden))
    flat = x.reshape(-1, arch.hidden)
    with jax.default_matmul_precision("highest"):
        uncut, loads = hybrid.experts_mixer(p, x, whole)
        assert int(loads.sum()) == flat.shape[0] * arch.top_k
        total, pairs = 0.0, 0
        for lo in range(0, n, per):
            share = dict(p, experts_up=p["experts_up"][lo:lo + per],
                         experts_down=p["experts_down"][lo:lo + per])
            out, counts = hybrid.experts_mixer(
                share, x, dataclasses.replace(
                    whole, held=tuple(range(lo, lo + per))))
            total = total + out.reshape(flat.shape)
            pairs += int(counts.sum())
    assert pairs == flat.shape[0] * arch.top_k
    _close(total, uncut.reshape(flat.shape), 1e-4)


def test_a_pad_position_is_not_routed(small, stack):
    _, _, arch, _ = small
    params, ids = stack
    p = _layer_of(params, small, "E")
    real = ids != 0
    x = jax.random.normal(jax.random.PRNGKey(9), ids.shape + (arch.hidden,))
    with jax.default_matmul_precision("highest"):
        every, all_counts = hybrid.experts_mixer(p, x, arch)
        words, counts = hybrid.experts_mixer(p, x, arch, real)
    assert int(all_counts.sum()) > int(counts.sum())
    assert np.array_equal(np.asarray(every)[np.asarray(real)],
                          np.asarray(words)[np.asarray(real)])
    # no shared expert: a PAD position's output is nought
    assert not np.asarray(words)[~np.asarray(real)].any()


# -- the estimator ---------------------------------------------------------------

def _texts(n=16, seed=0):
    rng = np.random.default_rng(seed)
    words = [f"w{i}" for i in range(300)]
    labels = rng.integers(0, 2, n)
    texts = [" ".join(rng.choice(words[100 * l:100 * l + 200],
                                 rng.integers(3, 40)))
             for l in labels]
    return Table({"text": texts, "label": labels})


def test_fit_transform_save_load_transform(small, tmp_path):
    config = small[0]
    table = _texts()
    est = DeepTextClassifier(
        hiddenSize=config["hidden_size"],
        numLayers=config["num_hidden_layers"],
        numHeads=config["num_attention_heads"], maxTokenLen=32,
        vocabSize=config["vocab_size"], batchSize=8, maxEpochs=2, seed=5,
        learningRate=1e-3,
        architecture=config["estimator"]["architecture"])
    model = est.fit(table)
    assert isinstance(model.trainer.model, hybrid.HybridBackbone)
    epochs = model.trainer.history
    assert epochs[0]["counters"]["tokens"] == 2 * 8 * 32
    loads = epochs[0]["counters"]["expertTokens"]
    assert len(loads) == 4 and len(loads[0]) == 4
    assert epochs[0]["counters"]["routedPairs"] == sum(map(sum, loads))
    # the bias is the seed's draw after two epochs of AdamW
    init = hybrid.HybridBackbone(model.trainer.model.arch, num_classes=2)
    from synapseml_tpu.dl.trainer import FlaxTrainer, TrainConfig

    first = FlaxTrainer(init, TrainConfig(seed=5)).init(
        np.zeros((1, 2), np.int32), jit=True).params
    for i in (5, 7, 9, 11):
        np.testing.assert_array_equal(
            np.asarray(model.trainer.params[f"layer_{i}"]["expert_bias"]),
            np.asarray(first[f"layer_{i}"]["expert_bias"]))
    out = model.transform(table)
    model.save(str(tmp_path / "m"))
    loaded = PipelineStage.load(str(tmp_path / "m"))
    assert loaded.get("architecture") == config["estimator"]["architecture"]
    again = loaded.transform(table)
    assert np.array_equal(out["prediction"], again["prediction"])
    np.testing.assert_allclose(out["probability"], again["probability"],
                               atol=1e-6)
