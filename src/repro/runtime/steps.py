"""Step builders: jit-wired train / prefill / decode steps for a (model, mesh).

``build_*`` return a :class:`StepBundle` holding the jitted function plus the
in/out shardings and ShapeDtypeStruct trees needed both to lower the step
without data (``.lower()`` on structs) and by live execution (device_put
real arrays to the same shardings).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models.base import BaseModel
from repro.runtime.optimizer import Optimizer, OptimizerConfig
from repro.runtime.sharding import ShardingRules, activation_rules, param_shardings


@dataclass
class StepBundle:
    fn: Callable  # jitted
    in_structs: tuple
    in_shardings: tuple
    out_shardings: Any
    rules: ShardingRules

    def lower(self):
        return self.fn.lower(*self.in_structs)


def _shard_tree(rules: ShardingRules, axes_tree, struct_tree):
    return rules.shardings(axes_tree, struct_tree)


def make_rules(mesh: Mesh, shape: ShapeConfig, *, zero: bool = True) -> ShardingRules:
    return ShardingRules.for_shape(
        mesh, kind=shape.kind, global_batch=shape.global_batch, zero=zero
    )


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def build_train_step(
    model: BaseModel,
    mesh: Mesh,
    shape: ShapeConfig,
    opt_cfg: OptimizerConfig | None = None,
    *,
    grad_accum: int | None = None,
    donate: bool = True,
) -> StepBundle:
    cfg = model.cfg
    opt = Optimizer(
        opt_cfg
        or OptimizerConfig(
            name=cfg.optimizer, moment_dtype=cfg.moment_dtype, first_moment=cfg.first_moment
        )
    )
    accum = grad_accum if grad_accum is not None else cfg.grad_accum
    # grad accumulators in param dtype: bf16 halves the accumulation buffer
    # for the trillion-param config (noise is amortized over few microbatches)
    accum_dtype = jnp.dtype(cfg.param_dtype)
    rules = make_rules(mesh, shape)

    p_shard = param_shardings(model, mesh)
    p_struct = model.param_struct()
    o_struct = opt.state_struct(p_struct)
    o_shard = rules.shardings(opt.state_axes(model.param_axes()), o_struct, is_param=True)
    b_struct = model.input_specs(shape)
    b_shard = _shard_tree(rules, model.input_axes(shape), b_struct)

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    def train_step(params, opt_state, batch):
        with activation_rules(rules):
            if accum <= 1:
                (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            else:
                # microbatch scan over the leading batch dim (activation
                # footprint / accum)
                def micro(carry, mb):
                    acc, lsum = carry
                    (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                    acc = jax.tree.map(
                        lambda a, gg: a + (gg / accum).astype(accum_dtype), acc, g
                    )
                    return (acc, lsum + l), None

                mbs = jax.tree.map(
                    lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]), batch
                )
                zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)
                (grads, lsum), _ = jax.lax.scan(micro, (zeros, jnp.float32(0.0)), mbs)
                loss = lsum / accum
                metrics = {}
            new_params, new_opt, stats = opt.update(grads, opt_state, params)
        metrics = dict(metrics, loss=loss, **stats)
        return new_params, new_opt, metrics

    # metrics are scalars -> replicated
    out_metrics = jax.eval_shape(train_step, p_struct, o_struct, b_struct)[2]
    metric_shard = jax.tree.map(lambda _: NamedSharding(mesh, P()), out_metrics)

    fn = jax.jit(
        train_step,
        in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard, metric_shard),
        donate_argnums=(0, 1) if donate else (),
    )
    return StepBundle(
        fn=fn,
        in_structs=(p_struct, o_struct, b_struct),
        in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard, metric_shard),
        rules=rules,
    )


# ---------------------------------------------------------------------------
# serve: prefill & decode
# ---------------------------------------------------------------------------


def _serving_zero(model: BaseModel, mesh: Mesh) -> bool:
    """Serving shards weights over the batch axes too when the model-axis
    shard alone would not fit HBM (the 1T config); small models keep weights
    replicated across data shards to avoid per-layer gathers."""
    from repro.utils.tree import tree_bytes

    per_chip = tree_bytes(model.param_struct()) / mesh.shape.get("model", 1)
    return per_chip > 8e9


def build_prefill_step(model: BaseModel, mesh: Mesh, shape: ShapeConfig) -> StepBundle:
    zero = _serving_zero(model, mesh)
    rules = make_rules(mesh, shape, zero=zero)
    p_shard = param_shardings(model, mesh, zero=zero)
    p_struct = model.param_struct()
    b_struct = model.input_specs(shape)
    b_shard = _shard_tree(rules, model.input_axes(shape), b_struct)

    def prefill(params, batch):
        with activation_rules(rules):
            logits, cache = model.prefill(params, batch)
        return logits, cache

    out_struct = jax.eval_shape(prefill, p_struct, b_struct)
    logits_shard = NamedSharding(mesh, rules.spec(("batch", None, None), out_struct[0].shape))
    # prefill cache has the same tree as cache_struct (sequence = prompt len)
    cache_shard = rules.shardings(model.cache_axes(shape), out_struct[1])
    fn = jax.jit(
        prefill,
        in_shardings=(p_shard, b_shard),
        out_shardings=(logits_shard, cache_shard),
    )
    return StepBundle(fn, (p_struct, b_struct), (p_shard, b_shard), (logits_shard, cache_shard), rules)


def build_decode_step(model: BaseModel, mesh: Mesh, shape: ShapeConfig, *, donate: bool = True) -> StepBundle:
    zero = _serving_zero(model, mesh)
    rules = make_rules(mesh, shape, zero=zero)
    p_shard = param_shardings(model, mesh, zero=zero)
    p_struct = model.param_struct()
    c_struct = model.cache_struct(shape)
    c_shard = rules.shardings(model.cache_axes(shape), c_struct)
    b_struct = model.input_specs(shape)
    b_shard = _shard_tree(rules, model.input_axes(shape), b_struct)

    def decode(params, cache, batch):
        with activation_rules(rules):
            logits, cache = model.decode(params, cache, batch)
        return logits, cache

    out_struct = jax.eval_shape(decode, p_struct, c_struct, b_struct)
    logits_shard = NamedSharding(mesh, rules.spec(("batch", None, None), out_struct[0].shape))
    fn = jax.jit(
        decode,
        in_shardings=(p_shard, c_shard, b_shard),
        out_shardings=(logits_shard, c_shard),
        donate_argnums=(1,) if donate else (),
    )
    return StepBundle(
        fn, (p_struct, c_struct, b_struct), (p_shard, c_shard, b_shard), (logits_shard, c_shard), rules
    )


# ---------------------------------------------------------------------------
# paged serve: prefill & decode against a page pool (repro.serving)
# ---------------------------------------------------------------------------


def _check_paged(model: BaseModel) -> None:
    if not getattr(model, "SUPPORTS_PAGED", False) or getattr(model, "is_vlm", False):
        raise ValueError(
            f"{type(model).__name__} does not support the paged serving path "
            "(needs last_pos prefill + the standard (L,B,S,KV,hd) cache tree)"
        )


def build_paged_prefill_step(model: BaseModel, *, page_size: int, donate: bool = True) -> Callable:
    """Jitted prefill that writes the prompt cache straight into pool pages.

    ``fn(params, k_pages, v_pages, tokens, last_pos, table) -> (next_tok,
    k_pages, v_pages)`` with ``tokens``: (B, S) rows right-padded to a bucket
    that is a multiple of ``page_size``, ``last_pos``: (B,) index of each
    row's true last prompt token, ``table``: (B, S // page_size) physical
    page ids covering each row's whole bucket (padding rows/columns point at
    scratch page 0, whose writes are absorbed). One compile per (row bucket,
    prompt bucket) pair — a step's joiners prefill as one stacked call.
    Pages are donated: the caller re-assigns ``k_pages/v_pages`` from the
    result every call.
    """
    _check_paged(model)
    ps = int(page_size)

    def prefill(params, k_pages, v_pages, tokens, last_pos, table):
        B, S = tokens.shape
        logits, cache = model.prefill(
            params, {"tokens": tokens, "last_pos": last_pos})
        # scatter the (L, B, S, KV, hd) prompt cache into each row's pages;
        # flattening (B, S//ps) row-major keeps page blocks aligned with the
        # flattened table, and duplicate scratch-page indices may collide —
        # page 0 is never read
        def to_pages(pages, dense):
            L, _, _, KV, hd = dense.shape
            paged = dense.reshape(L, B * (S // ps), ps, KV, hd)
            return pages.at[:, table.reshape(-1)].set(paged.astype(pages.dtype))

        k_pages = to_pages(k_pages, cache["k"])
        v_pages = to_pages(v_pages, cache["v"])
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)  # (B,)
        return next_tok, k_pages, v_pages

    return jax.jit(prefill, donate_argnums=(1, 2) if donate else ())


def build_paged_decode_step(
    model: BaseModel,
    *,
    page_size: int,
    use_kernel: bool = False,
    interpret: bool | None = None,
    donate: bool = True,
    quantum: int = 1,
) -> Callable:
    """Jitted decode over gathered pages, one dispatch per scheduling quantum.

    With ``quantum=1`` (the default): ``fn(params, k_pages, v_pages, tokens,
    positions, table) -> (next_tok, k_pages, v_pages)`` with ``tokens``:
    (B, 1) current token per live row, ``positions``: (B,) write index
    (= live length) per row, ``table``: (B, max_pages) page ids padded with
    the scratch page 0. Gathers each row's logical context ``table ->
    (B, max_pages*page_size)`` dense view, runs ``model.decode``, and
    scatters only the new K/V entry back into the row's live page. Padded
    rows/entries resolve to page 0 — garbage that ``positions`` masks on
    read and scratch writes absorb. Compiles once per (batch-bucket,
    pages-bucket) pair.

    With ``quantum=q > 1`` ONE dispatch emits q greedy tokens per live row:
    ``fn(..., table, left) -> (tokens (B, q), k_pages, v_pages)`` where
    ``left``: (B,) tokens remaining in each row's output budget. The pages
    are gathered ONCE into a dense per-row context, a ``lax.scan`` decodes q
    steps against that small dense cache (the full pools stay out of the
    scan carry — carrying them would copy every page each iteration), and
    all q new K/V entries scatter back in a single pool update. Entries with
    ``s >= left[row]`` redirect to scratch page 0, so a row can never write
    past its page reservation; the host discards the surplus tokens (greedy
    decode is prefix-stable, so the kept prefix is identical to stepping one
    token at a time). This amortizes the per-dispatch host overhead that
    dominates one-token-per-call serving of small models, at the cost of
    joiners waiting up to q steps to enter.

    ``use_kernel`` routes decode attention through the Pallas kernel
    (trace-time scope; ``block_kv = page_size`` so cache chunks line up with
    pages and the early exit skips unwritten ones).
    """
    _check_paged(model)
    ps = int(page_size)
    q = max(int(quantum), 1)

    def one(params, k_pages, v_pages, tokens, positions, table, write):
        B, mp = table.shape
        L, _, _, KV, hd = k_pages.shape

        def gather(pages):
            return pages[:, table].reshape(L, B, mp * ps, KV, hd)

        cache = {"k": gather(k_pages), "v": gather(v_pages)}
        logits, cache = model.decode(
            params, cache, {"tokens": tokens, "positions": positions})
        # scatter back only the entry model.decode wrote at ``positions``;
        # rows past their budget (write=False) land in scratch page 0, and
        # the index clamps keep over-budget positions in bounds (their
        # values are discarded anyway)
        rows = jnp.arange(B)
        pg = jnp.where(write, table[rows, jnp.minimum(positions // ps, mp - 1)], 0)
        off = jnp.where(write, positions % ps, 0)

        def scatter(pages, dense):
            new = dense[:, rows, jnp.minimum(positions, mp * ps - 1)]  # (L, B, KV, hd)
            return pages.at[:, pg, off].set(new.astype(pages.dtype))

        k_pages = scatter(k_pages, cache["k"])
        v_pages = scatter(v_pages, cache["v"])
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)  # (B,)
        return next_tok, k_pages, v_pages

    if q == 1:
        def decode(params, k_pages, v_pages, tokens, positions, table):
            B = table.shape[0]
            return one(params, k_pages, v_pages, tokens, positions, table,
                       jnp.ones((B,), bool))
    else:
        def decode(params, k_pages, v_pages, tokens, positions, table, left):
            B, mp = table.shape
            L, _, _, KV, hd = k_pages.shape

            def gather(pages):
                return pages[:, table].reshape(L, B, mp * ps, KV, hd)

            cache = {"k": gather(k_pages), "v": gather(v_pages)}

            def body(carry, _):
                tok, pos, cache = carry
                logits, cache = model.decode(
                    params, cache, {"tokens": tok, "positions": pos})
                nt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return (nt[:, None], pos + 1, cache), nt

            (_, _, cache), toks = jax.lax.scan(
                body, (tokens, positions, cache), None, length=q)
            # one masked scatter of all q new entries per row back into the
            # pool; over-budget steps land in scratch page 0, index clamps
            # keep out-of-range positions in bounds (values discarded)
            rows = jnp.arange(B)[:, None]  # (B, 1)
            steps = jnp.arange(q)[None, :]  # (1, q)
            pos_q = positions[:, None] + steps  # (B, q)
            write = steps < left[:, None]
            pg = jnp.where(
                write, table[rows, jnp.minimum(pos_q // ps, mp - 1)], 0)
            off = jnp.where(write, pos_q % ps, 0)

            def scatter(pages, dense):
                # dense (L, B, mp*ps, KV, hd) -> the q freshly decoded slots
                new = jnp.take_along_axis(
                    dense, jnp.minimum(pos_q, mp * ps - 1)[None, :, :, None, None],
                    axis=2)  # (L, B, q, KV, hd)
                flat = new.reshape(L, B * q, KV, hd)
                return pages.at[:, pg.reshape(-1), off.reshape(-1)].set(
                    flat.astype(pages.dtype))

            k_pages = scatter(k_pages, cache["k"])
            v_pages = scatter(v_pages, cache["v"])
            return toks.T, k_pages, v_pages  # (B, q)

    if use_kernel:
        from repro.models.attention import decode_kernel_scope

        inner = decode

        def decode_with_kernel(params, k_pages, v_pages, *rest):
            # trace-time routing: jit traces this body once per shape, and the
            # scope is active during that trace, baking the kernel into HLO
            with decode_kernel_scope(block_kv=ps, interpret=interpret):
                return inner(params, k_pages, v_pages, *rest)

        decode = decode_with_kernel

    return jax.jit(decode, donate_argnums=(1, 2) if donate else ())


def build_step(model: BaseModel, mesh: Mesh, shape: ShapeConfig, **kw) -> StepBundle:
    """Dispatch on the shape kind (train_step vs serve_step)."""
    if shape.kind == "train":
        return build_train_step(model, mesh, shape, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(model, mesh, shape)
    return build_decode_step(model, mesh, shape)
