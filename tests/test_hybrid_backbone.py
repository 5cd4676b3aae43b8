"""The hybrid text backbone (dl/hybrid.py) against its plain reference
(benchmark/references/nemotron3_nano_ft.py) at a small size on the CPU, seeded
weights, float32: every block and the whole stack, forward and gradient; the
chunked scan against the recurrence; causality and padding; routing; the
chip's share of the experts against the uncut layer; the estimator."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as harness
from synapseml_tpu.core import PipelineStage, Table
from synapseml_tpu.dl import hybrid
from synapseml_tpu.dl.text import DeepTextClassifier, TransformerEncoder
from synapseml_tpu.dl.trainer import FlaxTrainer, TrainConfig

CELL = "nemotron3_nano_fit"


@pytest.fixture(scope="module")
def small():
    """(configuration at the rehearsal's sizes, the reference's module, the
    program's architecture, the reference's sizes)."""
    _, _, config, _ = harness.load_cell(CELL, True)
    ref = harness._load_module("references", "nemotron3_nano_ft")
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


def _close(a, b, tol=2e-4):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-6), (
        np.abs(a - b).max(), np.abs(b).max())


def _layer_of(params, small, kind):
    i = small[2].pattern.index(kind)
    return {k: v for k, v in params[f"layer_{i}"].items() if k != "norm"}


BLOCKS = {
    "M": (lambda p, x, arch: hybrid.mamba2_mixer(p, x, arch),
          lambda ref, p, x, z: ref._state_space(p, x, z, lambda v: v,
                                                 ref.switches())),
    "*": (lambda p, x, arch: hybrid.attention_mixer(p, x, arch),
          lambda ref, p, x, z: ref._attention(p, x, z, lambda v: v)),
    "E": (lambda p, x, arch: hybrid.experts_mixer(p, x, arch)[0],
          lambda ref, p, x, z: ref._experts(
              p, x.reshape(-1, x.shape[-1]), z, lambda v: v, ref.switches()
          ).reshape(x.shape)),
}


@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_block_forward_and_gradient_match_the_reference(small, stack, kind):
    config, ref, arch, z = small
    p = _layer_of(stack[0], small, kind)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 21, arch.hidden))
    ours, theirs = BLOCKS[kind]
    with jax.default_matmul_precision("highest"):
        _close(ours(p, x, arch), theirs(ref, p, x, z))
        probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)
        g_ours = jax.grad(lambda p, x: (ours(p, x, arch) * probe).sum(),
                          (0, 1))(p, x)
        g_ref = jax.grad(lambda p, x: (theirs(ref, p, x, z) * probe).sum(),
                         (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g_ours), jax.tree.leaves(g_ref)):
        _close(a, b, 5e-4)


def test_stack_forward_and_gradient_match_the_reference(small, stack):
    config, ref, arch, _ = small
    params, ids = stack
    model = hybrid.HybridBackbone(arch, num_classes=2)
    y = jnp.asarray([0, 1, 1])

    def loss(p):
        logits = model.apply({"params": p}, ids, train=True)
        return -jnp.take_along_axis(jax.nn.log_softmax(logits), y[:, None],
                                    -1).sum()

    with jax.default_matmul_precision("highest"):
        _close(jax.jit(lambda p: model.apply({"params": p}, ids))(params),
               jax.jit(lambda p: ref.forward(p, ids, config))(params))
        ours = jax.jit(jax.grad(loss))(params)
        theirs = jax.jit(jax.grad(lambda p: ref.loss_sum(
            p, ids, y, config, "float32", None)))(params)
    flat_o, flat_r = ref.flatten(ours), ref.flatten(theirs)
    assert set(flat_o) == set(flat_r)
    for k in flat_r:
        _close(flat_o[k], flat_r[k], 1e-3)


def test_initial_parameters_are_the_references(small):
    config, ref, arch, _ = small
    model = hybrid.HybridBackbone(arch, num_classes=2)
    # as the estimator initialises it: one compiled program, two positions
    ours = FlaxTrainer(model, TrainConfig(seed=11)).init(
        np.zeros((1, 2), np.int32), jit=True).params
    theirs = ref.initial_parameters(config, 11, 2)
    flat_o, flat_r = ref.flatten(ours), ref.flatten(theirs)
    assert set(flat_o) == set(flat_r)
    for k in flat_r:
        # a compiled initializer may round its last bit otherwise
        np.testing.assert_allclose(np.asarray(flat_o[k]),
                                   np.asarray(flat_r[k]), rtol=2e-6,
                                   atol=1e-9, err_msg=k)


def _recurrence(x, dt, a, b, c):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t."""
    bsz, length, heads, p = x.shape
    per = heads // b.shape[2]
    state = np.zeros((bsz, heads, p, b.shape[3]))
    out = np.zeros(x.shape)
    for t in range(length):
        bt, ct = (np.repeat(v[:, t], per, axis=1) for v in (b, c))
        state = (np.exp(dt[:, t] * a)[..., None, None] * state
                 + (dt[:, t, :, None] * x[:, t])[..., None] * bt[:, :, None])
        out[:, t] = np.einsum("bhpn,bhn->bhp", state, ct)
    return out


@pytest.mark.parametrize("length", [16, 24, 19, 5])
def test_chunked_scan_is_the_recurrence(length):
    rng = np.random.default_rng(length)
    x = rng.normal(size=(2, length, 4, 3))
    dt = rng.uniform(0.01, 0.5, size=(2, length, 4))
    a = -rng.uniform(0.5, 4.0, size=4)
    b, c = rng.normal(size=(2, 2, length, 2, 5))
    args = [jnp.asarray(v, jnp.float32) for v in (x, dt, a, b, c)]
    with jax.default_matmul_precision("highest"):
        got = hybrid.ssd_chunked(*args, chunk=8)
        # right padding (dt = 0 there) changes nothing before it
        padded = [jnp.pad(v, ((0, 0), (0, 6)) + ((0, 0),) * (v.ndim - 2))
                  if v.ndim > 1 else v for v in args]
        got_padded = hybrid.ssd_chunked(*padded, chunk=8)
    _close(got, _recurrence(x, dt, a, b, c), 1e-4)
    _close(got_padded[:, :length], got, 1e-5)


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


def test_pad_after_the_last_word_changes_nothing(small, stack):
    _, _, arch, _ = small
    params, ids = stack
    model = hybrid.HybridBackbone(arch, num_classes=2)
    apply = jax.jit(lambda ids: model.apply({"params": params}, ids))
    whole = apply(ids)
    _close(apply(ids[2:, :5])[0], whole[2], 1e-5)      # the row unpadded
    _close(apply(jnp.pad(ids, ((0, 0), (0, 11)))), whole, 1e-5)


def test_routing_takes_top_k_and_weights_sum_to_the_scaling(small, stack):
    _, _, arch, _ = small
    p = _layer_of(stack[0], small, "E")
    x = jax.random.normal(jax.random.PRNGKey(4), (50, arch.hidden))
    ids, w = hybrid.route(x, p["router"], arch)
    ids, w = np.asarray(ids), np.asarray(w)
    assert ids.shape == (50, arch.top_k)
    assert all(len(set(row)) == arch.top_k for row in ids)
    assert ids.min() >= 0 and ids.max() < arch.experts
    np.testing.assert_allclose(w.sum(-1), arch.scaling, rtol=1e-5)
    assert (w > 0).all()


def test_no_pair_is_dropped_when_one_expert_takes_every_token(small, stack,
                                                              monkeypatch):
    _, ref, arch, _ = small
    # segments of 16 rows: the one expert's pairs fill seven of them
    monkeypatch.setattr(hybrid, "segment_rows", lambda *_: 16)
    p = dict(_layer_of(stack[0], small, "E"))
    # a router that sends every token to the held expert 2 first
    p["router"] = p["router"].at[:, 2].set(0.0) * 0.0 + jnp.zeros_like(
        p["router"]).at[:, 2].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (3, 37,
                                                          arch.hidden)))
    with jax.default_matmul_precision("highest"):
        flat = x.reshape(-1, arch.hidden)
        ids, w = hybrid.route(flat, p["router"], arch)
        routed, counts = hybrid.held_experts_part(
            flat, ids, w, p["experts_up"], p["experts_down"], arch.held,
            arch.experts)
        assert int(counts[2]) == flat.shape[0]
        assert int(counts.sum()) == int(np.isin(np.asarray(ids),
                                                arch.held).sum())
        want = sum(
            jnp.where(ids == e, w, 0.0).sum(-1, keepdims=True)
            * hybrid._expert(flat, p["experts_up"][j], p["experts_down"][j])
            for j, e in enumerate(arch.held))
    _close(routed, want, 1e-4)


def test_the_shares_add_up_to_the_uncut_layer(small, stack):
    """The routed parts of all the shares, with the shared expert counted
    once, are the layer with every expert held (guide section 4)."""
    _, _, arch, _ = small
    key = jax.random.PRNGKey(6)
    n, per = arch.experts, len(arch.held)
    whole = dataclasses.replace(arch, held=tuple(range(n)))
    p = dict(_layer_of(stack[0], small, "E"))
    p["experts_up"] = 0.1 * jax.random.normal(
        key, (n,) + p["experts_up"].shape[1:])
    p["experts_down"] = 0.1 * jax.random.normal(
        jax.random.fold_in(key, 1), (n,) + p["experts_down"].shape[1:])
    x = jax.random.normal(jax.random.fold_in(key, 2), (2, 23, arch.hidden))
    flat = x.reshape(-1, arch.hidden)
    with jax.default_matmul_precision("highest"):
        uncut, loads = hybrid.experts_mixer(p, x, whole)
        assert int(loads.sum()) == flat.shape[0] * arch.top_k
        shared = hybrid._expert(flat, p["shared_up"], p["shared_down"])
        total, pairs = shared, 0
        for lo in range(0, n, per):
            held = tuple(range(lo, lo + per))
            share = dict(p, experts_up=p["experts_up"][lo:lo + per],
                         experts_down=p["experts_down"][lo:lo + per])
            out, counts = hybrid.experts_mixer(
                share, x, dataclasses.replace(arch, held=held))
            total = total + (out.reshape(flat.shape) - shared)
            pairs += int(counts.sum())
    assert pairs == flat.shape[0] * arch.top_k
    _close(total, uncut.reshape(flat.shape), 1e-4)


# -- the estimator ---------------------------------------------------------------

def _texts(n=24, seed=0):
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
    assert epochs[0]["counters"]["tokens"] == 3 * 8 * 32
    assert epochs[0]["counters"]["routedPairs"] == sum(
        map(sum, epochs[0]["counters"]["expertTokens"]))
    measures = model.trainer.stats["measures"]
    assert measures["count:tokens"] == 2 * 3 * 8 * 32
    assert 0 < measures["count:padTokens"] < measures["count:tokens"]
    out = model.transform(table)
    model.save(str(tmp_path / "m"))
    loaded = PipelineStage.load(str(tmp_path / "m"))
    assert loaded.get("architecture") == config["estimator"]["architecture"]
    again = loaded.transform(table)
    assert np.array_equal(out["prediction"], again["prediction"])
    np.testing.assert_allclose(out["probability"], again["probability"],
                               atol=1e-6)


def test_a_saved_dense_model_still_loads(tmp_path):
    table = _texts()
    model = DeepTextClassifier(hiddenSize=32, numLayers=2, numHeads=4,
                               maxTokenLen=16, vocabSize=256, batchSize=8,
                               seed=1).fit(table)
    assert isinstance(model.trainer.model, TransformerEncoder)
    model.save(str(tmp_path / "dense"))
    loaded = PipelineStage.load(str(tmp_path / "dense"))
    assert loaded.get("architecture") is None
    assert isinstance(loaded.trainer.model, TransformerEncoder)
    assert np.array_equal(model.transform(table)["prediction"],
                          loaded.transform(table)["prediction"])


class _CountingEncoder(TransformerEncoder):
    """The dense encoder, counting its tokens as the hybrid backbone does:
    switches the trainer's counter path on and changes nothing else."""

    def __call__(self, ids, train: bool = True):
        self.sow("counters", "tokens", jnp.asarray(ids.size, jnp.int32),
                 reduce_fn=jnp.add, init_fn=lambda: jnp.zeros((), jnp.int32))
        return super().__call__(ids, train)


def test_the_dense_encoders_steps_are_bit_for_bit_with_and_without_counters():
    rng = np.random.default_rng(3)
    ids = rng.integers(2, 200, (24, 16)).astype(np.int32)
    ids[:, 0] = 1
    ids[:, 11:] = 0
    y = rng.integers(0, 2, 24)
    kw = dict(vocab_size=256, num_layers=2, num_heads=4, hidden=32,
              max_len=16)
    cfg = dict(batch_size=8, max_epochs=1, learning_rate=1e-3,
               optimizer="adamw", seed=9)
    plain = FlaxTrainer(TransformerEncoder(**kw), TrainConfig(**cfg))
    plain.fit(ids, y)
    counting = FlaxTrainer(_CountingEncoder(**kw), TrainConfig(**cfg))
    counting.fit(ids, y)
    assert plain.counters == {} and "counters" not in plain.history[0]
    assert counting.history[0]["counters"] == {"tokens": 3 * 8 * 16}
    assert plain.history[0]["steps"] == 3
    a, b = jax.tree.leaves(plain.params), jax.tree.leaves(counting.params)
    assert len(a) == len(b)
    for p, q in zip(a, b):
        assert np.array_equal(np.asarray(p), np.asarray(q))


def test_the_initial_weights_follow_the_estimators_seed(small):
    config = small[0]
    table = _texts(8)

    def first_step_inputs(seed):
        kept = {}

        def hook(step, loss, params, batch_stats, opt_state):
            kept["embed"] = np.asarray(params["tok_embed"]["embedding"])
            raise StopIteration

        est = DeepTextClassifier(
            hiddenSize=config["hidden_size"],
            numLayers=config["num_hidden_layers"],
            numHeads=config["num_attention_heads"], maxTokenLen=16,
            vocabSize=config["vocab_size"], batchSize=8, seed=seed,
            learningRate=0.0, optimizer="sgd",
            architecture=config["estimator"]["architecture"], stepFn=hook)
        with pytest.raises(StopIteration):
            est.fit(table)
        return kept["embed"]

    assert np.array_equal(first_step_inputs(1), first_step_inputs(1))
    assert not np.array_equal(first_step_inputs(1), first_step_inputs(2))


def test_a_pad_position_is_not_routed(small, stack):
    """The held experts' counts are the words' pairs, and no word's output
    moves: the layer with PAD routed and without differ at PAD alone."""
    _, _, arch, _ = small
    params, ids = stack
    p = _layer_of(params, small, "E")
    real = ids != 0
    x = jax.random.normal(jax.random.PRNGKey(8), ids.shape + (arch.hidden,))
    with jax.default_matmul_precision("highest"):
        every, all_counts = hybrid.experts_mixer(p, x, arch)
        words, counts = hybrid.experts_mixer(p, x, arch, real)
        chosen, _ = hybrid.route(x.reshape(-1, arch.hidden), p["router"], arch)
    chosen = np.asarray(chosen).reshape(ids.shape + (arch.top_k,))
    held = np.isin(chosen, arch.held)
    assert int(counts.sum()) == int(held[np.asarray(real)].sum())
    assert int(all_counts.sum()) == int(held.sum()) > int(counts.sum())
    assert np.array_equal(np.asarray(every)[np.asarray(real)],
                          np.asarray(words)[np.asarray(real)])
    shared = hybrid._expert(x.reshape(-1, arch.hidden), p["shared_up"],
                            p["shared_down"]).reshape(x.shape)
    _close(np.asarray(words)[~np.asarray(real)],
           np.asarray(shared)[~np.asarray(real)], 1e-6)


@pytest.mark.parametrize("pairs, held, experts, rows", [
    (16384 * 6, 8, 128, 12288),     # two even loads of a sixteenth
    (333, 4, 16, 168),              # 166.5 pairs, to eight rows
    (96, 16, 16, 96),               # every expert held: one segment
    (5, 1, 128, 5),                 # never more rows than pairs
])
def test_a_segment_is_the_held_experts_even_load(pairs, held, experts, rows):
    assert hybrid.segment_rows(pairs, held, experts) == rows
