"""Pieces shared by the plain references: the benchmark's own weights, the
matmul at a stated precision, norms, and the AdamW steps whose readings
the program is held to.

Nothing here imports the program. The weights are the benchmark's: each
leaf is drawn from the seed and its own path, so the program's state and
the reference start from the same values without either taking them from
the other.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (PRNGKey alone keeps 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# weights


def _draw(key, shape, kind, fan_in):
    """One leaf. Norm scales and biases are drawn away from their identity
    values so that the reference checks how each is applied."""
    n = jax.random.normal(key, shape, jnp.float32)
    if kind == "w":                       # dense weight, fan-in scaled
        return n / np.sqrt(fan_in)
    if kind == "b":                       # bias
        return 0.02 * n
    if kind == "ln_scale":                # LayerNorm scale, stored as is
        return 1.0 + 0.05 * n
    if kind == "rms_scale":               # RMSNorm scale, stored as scale - 1
        return 0.05 * n
    if kind == "beta":                    # branch scalar near 1
        return 1.0 + 0.05 * n
    if kind == "a_log":                   # S4D-real A = 1..d_state, jittered
        ds = shape[-1]
        base = jnp.log(jnp.arange(1, ds + 1, dtype=jnp.float32))
        return base + 0.05 * n
    if kind == "dt_bias":                 # softplus^-1 of dt in [1e-3, 1e-1]
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(np.log(1e-3) + u * (np.log(1e-1) - np.log(1e-3)))
        return jnp.log(jnp.expm1(dt))
    if kind == "uniform":                 # dt_proj: U(-r^-1/2, r^-1/2)
        u = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
        return u / np.sqrt(fan_in)
    if kind == "one":                     # skip gain D near 1
        return 1.0 + 0.05 * n
    raise ValueError(kind)


def init_tree(specs, key):
    """{path: (shape, kind, fan_in, dtype)} -> nested dict of leaves. A
    path part that is a number indexes a list (the program's segment
    lists)."""
    out = {}
    for path, (shape, kind, fan_in, dtype) in specs.items():
        k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
        leaf = _draw(k, shape, kind, fan_in).astype(dtype)
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return _lists(out)


def _lists(node):
    if not isinstance(node, dict):
        return node
    node = {k: _lists(v) for k, v in node.items()}
    if node and all(k.isdigit() for k in node):
        return [node[str(i)] for i in range(len(node))]
    return node


def layer_runs(kinds, boundary):
    """Consecutive runs of one layer kind, split at the frozen/trainable
    boundary: ([(kind, count)] frozen, [(kind, count)] trainable)."""
    runs = []
    for i, k in enumerate(kinds):
        if runs and runs[-1][0] == k and i != boundary:
            runs[-1][1] += 1
        else:
            runs.append([k, 1])
    frozen, train, seen = [], [], 0
    for k, c in runs:
        (frozen if seen < boundary else train).append((k, c))
        seen += c
    return frozen, train


# ---------------------------------------------------------------------------
# arithmetic


def fp8_round(x):
    """Round to float8_e4m3fn on the way forward, pass gradients through:
    the control's matmul operands."""
    r = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x + jax.lax.stop_gradient(r - x)


def make_mm(precision: str):
    """einsum in float32 at HIGHEST precision, or (the control) with both
    operands rounded to fp8 first."""
    if precision == "float32":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "fp8":
        return lambda eq, a, b: jnp.einsum(eq, fp8_round(a), fp8_round(b),
                                           precision=HIGHEST)
    raise ValueError(precision)


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def rms_norm(x, scale_minus_one, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * (1.0 + scale_minus_one)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def cross_entropy(logits, labels):
    """Per-row CE of f32 logits [..., C] against int labels [...]."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - gold


def f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# ---------------------------------------------------------------------------
# steps and readings


def leaf_norms(tree):
    """{path: L2 norm} of a tree's leaves, keyed by `jax.tree_util.keystr`."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for p, v in flat}


def _adamw(params, grads, mu, nu, t, opt):
    """Clip by global norm, then one AdamW step with decay on every leaf."""
    gsq = sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(jnp.sqrt(gsq),
                                                             1e-9))
    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, nu,
                                grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m, v):
        step = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        return p - opt["lr"] * (step + opt["weight_decay"] * p)

    return jax.tree_util.tree_map(upd, params, mu, nu), mu, nu, grads


def make_step(client_loss, opt, n_clients):
    """One jitted reference step: (params, mu, nu, t, frozen, batch) ->
    (params, mu, nu, loss, {leaf: norm of the clipped gradient}), with
    params and moments donated.

    `client_loss(params, frozen, batch, n)` is client n's weighted share of
    the step's loss; the gradient is summed over clients one at a time, so
    that only one client's activations are live."""
    def step(params, mu, nu, t, frozen, batch):
        def one(acc, n):
            loss, grads = jax.value_and_grad(client_loss)(params, frozen,
                                                          batch, n)
            return jax.tree_util.tree_map(jnp.add, acc, (loss, grads)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree_util.tree_map(jnp.zeros_like, params))
        (loss, grads), _ = jax.lax.scan(one, zero, jnp.arange(n_clients))
        params, mu, nu, grads = _adamw(params, grads, mu, nu, t, opt)
        return params, mu, nu, loss, leaf_norms(grads)
    return jax.jit(step, donate_argnums=(0, 1, 2))


def readings(client_loss, params, frozen, batches, opt, n_clients):
    """Run AdamW over `batches` from `params` (float32) and return what the
    program is compared on: each step's loss, each leaf's norm of the first
    clipped gradient, and each leaf's norm of the change after the last
    step; and the starting params."""
    step = make_step(client_loss, opt, n_clients)
    p0 = params
    params = jax.tree_util.tree_map(jnp.copy, params)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad = [], None
    for i, batch in enumerate(batches):
        params, mu, nu, loss, gn = step(params, mu, nu,
                                        jnp.float32(i + 1), frozen, batch)
        losses.append(float(loss))
        if grad is None:
            grad = {k: float(v) for k, v in gn.items()}
    del mu, nu
    delta = delta_norms(params, p0)
    return {"losses": losses, "grad": grad, "delta": delta}, p0


@jax.jit
def _delta(a, b):
    return leaf_norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def delta_norms(params, p0):
    return {k: float(v) for k, v in _delta(params, p0).items()}


@functools.partial(jax.jit, static_argnames=("b1",))
def _grad_from_mu(mu, b1):
    return leaf_norms(jax.tree_util.tree_map(lambda m: m / (1.0 - b1), mu))


def grad_norms_from_moment(mu, b1: float):
    """Each leaf's norm of the first clipped gradient, worked out from the
    first Adam moment after one step: mu_1 = (1 - b1) g_1."""
    return {k: float(v) for k, v in _grad_from_mu(mu, float(b1)).items()}
