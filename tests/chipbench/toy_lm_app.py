"""A second app for the benchmark's harness, for the tests: the model zoo's
traced transformer (``repro.models.zoo.transformer_demo``), built and
served the way ``repro.launch.serve.serve_traced_transformer_demo``
builds it.

A request is one prompt: a ``[T, d]`` float32 sequence of embeddings,
with ``T`` from ``prompt_lengths``, so one pool holds items of several
shapes; its size is its token count.  The weights are drawn from the
run's seed here, on the device in one jitted call, and handed to the
program.  The check runs a plain ``jax.numpy`` float32 forward of the
same weights at the highest matmul precision and reads, at every position
of every compared prompt, how far the reference logit of the token the
served logits put first lies below the reference's best
(``max_logit_gap``), and the widest gap between any served logit and the
reference's (``max_logit_err``).  It imports nothing of the program.  The
control, the same forward in bfloat16 put in the served path's place,
mostly picks the reference's token, and so the logits themselves are
compared too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import frames
from chipbench.record import Served

REQUIRED = ("model", "prompt_lengths")
UNIT = "tokens"
EPS = 1e-6
# Readings on the CPU over 14 seeds, of the prompts served in a run and of
# the bfloat16 control on the pool's first eight (PERF.md).
# max_logit_gap: sound runs read 0 (the served token is the reference's
# best at every position); each forward's first residual add left out
# reads 4.7 and more; the control reads 0 to 0.022, so this number alone
# cannot tell bfloat16 from float32.
LOGIT_GAP_LIMIT = 0.05
# max_logit_err: sound runs read 1.4e-06 to 2.2e-06; the control 0.028 to
# 0.050.
LOGIT_ERR_LIMIT = 0.003


@functools.lru_cache(maxsize=None)
def _params_fn(n_layers: int, d: int, ff: int, vocab: int):
    def dense(k, shape):
        return jax.random.normal(k, shape, jnp.float32) * shape[0] ** -0.5

    def scale(k):
        return 0.1 * jax.random.normal(k, (d,), jnp.float32)

    def draw(seed):
        keys = iter(jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(seed), 0), 8 * n_layers + 2))
        layers = [{"ln1": scale(next(keys)),
                   "wq": dense(next(keys), (d, d)),
                   "wk": dense(next(keys), (d, d)),
                   "wv": dense(next(keys), (d, d)),
                   "wo": dense(next(keys), (d, d)),
                   "ln2": scale(next(keys)),
                   "wi": dense(next(keys), (d, 2 * ff)),
                   "wo_ffn": dense(next(keys), (ff, d))}
                  for _ in range(n_layers)]
        return {"layers": layers, "ln_f": scale(next(keys)),
                "w_out": dense(next(keys), (d, vocab))}

    return jax.jit(draw)


@functools.lru_cache(maxsize=None)
def _prompts_fn(lengths: tuple, d: int):
    def draw(seed):
        keys = jax.random.split(
            jax.random.fold_in(jax.random.PRNGKey(seed), 1), len(lengths))
        return tuple(jax.random.normal(k, (t, d), jnp.float32)
                     for k, t in zip(keys, lengths))

    return jax.jit(draw)


class Source:
    """Seeded prompts, the lengths of ``prompt_lengths`` in turn, and the
    model's weights."""

    def __init__(self, config: dict, seed: int):
        m = config["model"]
        self.model = m
        self.lengths = tuple(int(t) for t in config["prompt_lengths"])
        self.seed = frames.key_seed(seed)
        weights = _params_fn(m["n_layers"], m["d"], m["ff"], m["vocab"])(
            self.seed)
        self.params = dict(weights, n_heads=m["n_heads"], theta=m["theta"])

    def device_pool(self, n: int) -> list:
        lengths = tuple(self.lengths[i % len(self.lengths)]
                        for i in range(n))
        return list(_prompts_fn(lengths, self.model["d"])(self.seed))

    def host_pool(self, n: int) -> list:
        return [np.asarray(x) for x in jax.device_get(self.device_pool(n))]

    def warm_items(self) -> list:
        return self.device_pool(len(self.lengths))

    def size(self, item) -> int:
        return int(item.shape[0])


def inputs(config: dict, seed: int) -> Source:
    return Source(config, seed)


def served_model(lib, params):
    """The program's unmodified model over the interposable library."""
    from repro.models.zoo import transformer_demo

    return transformer_demo(lib, params)


def build(config: dict, source: Source, devices: list) -> Served:
    from repro.core import PipelineGenerator
    from repro.core.tracer import Frontend, Library
    from repro.launch.serve import RequestQueueServer
    from repro.models.zoo import make_zoo_db

    db = make_zoo_db()
    model = served_model(Library(db), source.params)
    warm = source.warm_items()
    ir, _ = Frontend(db).trace(model, warm[0])
    pipe = PipelineGenerator(db).generate(ir, policy="optimal", fuse=True,
                                          max_stages=4)
    ex = pipe.executor(microbatch=config["max_batch"], pad_microbatches=True)
    for x in warm:
        ex.warmup(x)
    srv = RequestQueueServer(ex, max_batch=config["max_batch"],
                             max_wait_ms=config["max_wait_ms"])
    return Served(ex, srv, [f"plan: {pipe.plan.n_stages} stages"])


def _rms(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * (
        1.0 + w)


def _rope(x, theta):
    t, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def reference_logits(params: dict, x):
    """Pre-norm transformer with causal RoPE attention and SwiGLU, in the
    type of ``x`` and the weights."""
    t, d = x.shape
    nh = params["n_heads"]
    with jax.default_matmul_precision("highest"):
        for ly in params["layers"]:
            h = _rms(x, ly["ln1"])
            q, k, v = ((h @ ly[w]).reshape(t, nh, d // nh)
                       for w in ("wq", "wk", "wv"))
            q, k = _rope(q, params["theta"]), _rope(k, params["theta"])
            s = jnp.einsum("thi,mhi->htm", q, k) / np.sqrt(d // nh)
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
            a = jnp.einsum("htm,mhi->thi", jax.nn.softmax(s, -1), v)
            x = x + a.reshape(t, d) @ ly["wo"]
            g, u = jnp.split(_rms(x, ly["ln2"]) @ ly["wi"], 2, -1)
            x = x + (jax.nn.silu(g) * u) @ ly["wo_ffn"]
        return _rms(x, params["ln_f"]) @ params["w_out"]


def logit_gap(served, ref) -> float:
    """Widest gap, over positions, between the reference's best logit and
    its logit of the token the served logits put first."""
    served, ref = np.asarray(served), np.asarray(ref)
    if served.shape != ref.shape or not np.isfinite(served).all():
        return float("inf")
    took = np.take_along_axis(ref, served.argmax(-1)[:, None], -1)[:, 0]
    return float(np.max(ref.max(-1) - took))


def logit_err(served, ref) -> float:
    """Widest gap between a served logit and the reference's."""
    served, ref = np.asarray(served), np.asarray(ref)
    if served.shape != ref.shape or not np.isfinite(served).all():
        return float("inf")
    return float(np.max(np.abs(served - ref)))


def control(items: list, source: Source) -> list:
    """The reference in bfloat16, the precision below the float32 the
    configuration states, in the served path's place: its logits for
    ``items``, as float32."""
    low = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if getattr(a, "dtype", None) == jnp.float32 else a, source.params)
    return [np.asarray(reference_logits(low, jnp.asarray(x, jnp.bfloat16)),
                       np.float32) for x in items]


def check(outputs: list, items: list, source: Source) -> dict:
    refs = {}
    for x in items:
        if id(x) not in refs:
            refs[id(x)] = reference_logits(source.params, jnp.asarray(x))
    pairs = [(out, refs[id(x)]) for out, x in zip(outputs, items)]
    return {"max_logit_gap": {"value": max((logit_gap(*p) for p in pairs),
                                           default=None),
                              "limit": LOGIT_GAP_LIMIT},
            "max_logit_err": {"value": max((logit_err(*p) for p in pairs),
                                           default=None),
                              "limit": LOGIT_ERR_LIMIT}}
