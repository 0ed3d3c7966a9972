"""GPT-2's forward pass, plain: ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, with no kernel, no cache and no
batching. It is the benchmark's own yardstick for ``correct`` and calls
nothing of the program under test; it only reads the weights by the names
``model.raw_params()`` gives them.

As published (Radford et al. 2019; ``openai-community/gpt2*``): learned
token and position embeddings, pre-LayerNorm blocks, causal softmax
attention, the tanh-approximate GELU (``gelu_new``), a final LayerNorm and
the output head tied to the token embedding. Departures: none in the
mathematics. The weights may arrive in bfloat16 (what the program serves);
each is cast to float32 inside the computation, which is exact, so no
float32 copy of the model is ever held. One block is one jitted program
called once a layer, so the compile is a layer's and not the model's.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

TABLE = "lm_head_weight"            # the tied token embedding
POSITIONS = "gpt.wpe.weight"
BLOCK = "gpt.blocks.{}."
BLOCK_LEAVES = ("ln1.weight", "ln1.bias", "attn.qkv.weight", "attn.qkv.bias",
                "attn.proj.weight", "attn.proj.bias", "ln2.weight",
                "ln2.bias", "mlp.fc1.weight", "mlp.fc1.bias",
                "mlp.fc2.weight", "mlp.fc2.bias")


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@jax.jit
def _embed(table, wpe, ids):
    table, wpe = _f32((table, wpe))
    return table[ids] + wpe[:ids.shape[-1]][None]


@functools.partial(jax.jit, static_argnames=("num_heads", "eps"))
def _block(p, x, num_heads, eps):
    p = _f32(p)
    b, t, h = x.shape
    hd = h // num_heads
    y = _layer_norm(x, p["ln1.weight"], p["ln1.bias"], eps)
    qkv = (y @ p["attn.qkv.weight"] + p["attn.qkv.bias"]
           ).reshape(b, t, 3, num_heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + a.reshape(b, t, h) @ p["attn.proj.weight"] + p["attn.proj.bias"]
    y = _layer_norm(x, p["ln2.weight"], p["ln2.bias"], eps)
    y = _gelu_new(y @ p["mlp.fc1.weight"] + p["mlp.fc1.bias"])
    return x + y @ p["mlp.fc2.weight"] + p["mlp.fc2.bias"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(g, b, table, x, eps):
    g, b, table = _f32((g, b, table))
    return _layer_norm(x, g, b, eps) @ table.T


def logits(params, ids, num_heads, eps=1e-5):
    """float32 logits ``[B, T, V]`` of token ids ``[B, T]``."""
    ids = jnp.asarray(ids, jnp.int32)
    n_layers = 1 + max(int(n.split(".")[2]) for n in params
                       if n.startswith("gpt.blocks."))
    with jax.default_matmul_precision("highest"):
        x = _embed(params[TABLE], params[POSITIONS], ids)
        for i in range(n_layers):
            pre = BLOCK.format(i)
            x = _block({leaf: params[pre + leaf] for leaf in BLOCK_LEAVES},
                       x, num_heads, eps)
        out = _head(params["gpt.ln_f.weight"], params["gpt.ln_f.bias"],
                    params[TABLE], x, eps)
    if out.dtype != jnp.float32:
        raise TypeError(f"the reference ran in {out.dtype}, not float32")
    return out


def row_logits(params, ids, width, num_heads, eps=1e-5):
    """Logits ``[len(ids), V]`` of ONE sequence, computed over a right-padded
    row of fixed ``width`` (causal: padding cannot reach earlier positions),
    so that every sequence of a run shares one compiled shape."""
    row = np.zeros((1, width), np.int32)
    row[0, :len(ids)] = ids
    return np.asarray(logits(params, row, num_heads, eps)[0, :len(ids)])


def loss(params, ids, num_heads, eps=1e-5):
    """Mean next-token cross-entropy over a batch ``[B, T]``, row by row."""
    total, count = 0.0, 0
    for row in np.asarray(ids):
        lg = logits(params, row[None], num_heads, eps)[0, :-1]
        logp = jax.nn.log_softmax(lg, -1)
        picked = jnp.take_along_axis(logp, jnp.asarray(row[1:, None]), -1)
        total += float(-picked.sum())
        count += len(row) - 1
    return total / count
