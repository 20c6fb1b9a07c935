"""The plain reference, held to the served model on the CPU at a small size.

Each cell's reference is its architecture's (``bench/archs/<arch>.py``). It
makes its weights again from the seed; they must be the served model's own,
bit for bit. Its float32 logits must then agree with the served model's
forward pass to within bfloat16 rounding."""
import jax
import jax.numpy as jnp
import numpy as np

import reference
import run
from conftest import benchmark, small_spec
from repro.models import transformer as tf

SEED = 2 ** 32 + 2 ** 31 + 3       # seeds may run past 32 bits


def small_cell():
    """The first cell's architecture and its configuration at CPU size."""
    spec = small_spec(run.cell_spec(benchmark()["workloads"][0]["name"]))
    return spec["arch"], spec["config"]


def test_seed_key_keeps_every_bit():
    a = reference.seed_key(7)
    b = reference.seed_key(2 ** 32 + 7)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_weights_are_the_served_models_bit_for_bit():
    arch, config = small_cell()
    params = tf.init_params(arch.model_config(config),
                            reference.seed_key(SEED), False)
    ref = arch.Reference(config, SEED)
    np.testing.assert_array_equal(np.asarray(params["embed"]),
                                  np.asarray(ref.outer["embed"]))
    np.testing.assert_array_equal(np.asarray(params["head"]),
                                  np.asarray(ref.outer["head"]))
    layers = params["layers"]
    for i, w in enumerate(ref.layers):
        for ours, theirs in (("wq", ("attn", "wq")), ("wk", ("attn", "wk")),
                             ("wv", ("attn", "wv")), ("wo", ("attn", "wo")),
                             ("wi", ("mlp", "wi")), ("wo_mlp", ("mlp", "wo"))):
            np.testing.assert_array_equal(
                np.asarray(layers[theirs[0]][theirs[1]][i]), np.asarray(w[ours]))
    for leaf in jax.tree.leaves({k: v for k, v in params.items()
                                 if k == "final_norm"}):
        assert not np.any(np.asarray(leaf))
    for g in ("ln1", "ln2"):
        assert not np.any(np.asarray(layers[g]["gamma"]))


def test_logits_agree_with_the_served_forward_pass():
    arch, config = small_cell()
    cfg = arch.model_config(config)
    params = tf.init_params(cfg, reference.seed_key(SEED), False)
    ref = arch.Reference(config, SEED)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, config["vocab_size"], 70).astype(np.int32)
    served, _, _ = tf.forward(params, cfg, tokens=jnp.asarray(toks[None]),
                              mode="train")
    rows = np.arange(len(toks))
    ours = ref.logits(toks, rows)
    theirs = np.asarray(served[0], np.float32)
    err = np.abs(ours - theirs).max() / np.abs(ours).max()
    assert err < 3e-2, err
    assert (ours.argmax(-1) == theirs.argmax(-1)).mean() > 0.9


def test_control_moves_the_logits_more_than_bf16():
    arch, config = small_cell()
    ref = arch.Reference(config, SEED)
    rng = np.random.default_rng(1)
    toks = rng.integers(0, config["vocab_size"], 70).astype(np.int32)
    rows = np.arange(len(toks))
    exact = ref.logits(toks, rows)
    fp8 = ref.logits(toks, rows, control=True)
    assert np.abs(exact - fp8).max() > 1e-2
