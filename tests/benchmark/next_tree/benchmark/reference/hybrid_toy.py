"""The dry run's reference: the four functions of the interface
(``benchmark/README.md``) as stubs that return shapes.  No family of the
program serves this configuration, so nothing compares against it; it shows
that a configuration's reference comes as a file of its own."""

import jax
import jax.numpy as jnp


def init_weights(hf, seed):
    h, v = hf["hidden_size"], hf["vocab_size"]
    return {"embed": jax.ShapeDtypeStruct((v, h), jnp.bfloat16),
            "lm_head": jax.ShapeDtypeStruct((h, v), jnp.bfloat16)}


def hidden(weights, hf, ids):
    return jax.ShapeDtypeStruct((len(ids), hf["hidden_size"]), jnp.float32)


def logits(weights, hf, x):
    return jax.ShapeDtypeStruct((x.shape[0], hf["vocab_size"]), jnp.float32)


def quantize(leaves, kind, hf):
    if kind != "fp8":
        raise KeyError(kind)
    return dict(leaves)
