"""Slot-pool machinery for the continuous-batching engine.

The pool is ONE fixed device allocation reused forever: a ``(max_batch,)``
slot-based KV/SSM cache plus per-slot ``tok``/``pos``/``done``/``eos``/
``budget`` vectors, all traced operands of three jitted programs —

* ``prefill``  — prefill one request's prompt at batch 1 (one compile per
  length bucket);
* ``admit``    — constant-shape scatter of that prefill row (cache, first
  sampled token, eos/budget/key) into a traced slot index (one compile per
  bucket, any slot / any admission pattern);
* ``chunk``    — K decode steps device-side as a ``lax.while_loop`` whose
  body does sample→append→done-mask for every slot in lock step (one
  compile, ever).  The host fetches only the reduced per-slot ``done``
  vector between chunks, so the per-token ``np.asarray`` sync of the static
  engine disappears.

Bitwise notes (all verified at fp32 on the CPU backend, pinned by
``tests/test_serving_continuous.py``): a per-slot cache — every leaf carrying
a slot axis, including a per-slot ``pos`` row — decoded through
``vmap`` over batch-1 ``decode_hidden`` calls is bitwise-identical to the
static lock-step batched decode, and a cache padded to the pool's fixed
length is bitwise-identical to an exact-length cache (pos = -1 slots mask to
exact zeros).  For per-slot *gathered* cluster weights the one operation
that breaks bitwise equality is the tied-embeddings logits einsum
``"bsd,vd->bsv"``; computing logits outside the vmap in the transposed
layout ``"bsd,bdv->bsv"`` against ``swapaxes(embed_stack, -2, -1)[d_vec]``
restores exact equality with the shared-weights path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import CausalLM
from repro.models.layers import softcap

__all__ = [
    "init_slot_state", "build_slot_programs", "add_batch_dim", "drop_batch_dim",
    "compile_count",
]


def _is_pos_leaf(path) -> bool:
    return getattr(path[-1], "key", None) == "pos"


def add_batch_dim(cache1):
    """Per-slot cache row -> batch-1 cache for ``model.decode_hidden``."""
    return jax.tree_util.tree_map_with_path(
        lambda p, x: x if _is_pos_leaf(p) else x[:, None], cache1
    )


def drop_batch_dim(cache):
    """Inverse of :func:`add_batch_dim`."""
    return jax.tree_util.tree_map_with_path(
        lambda p, x: x if _is_pos_leaf(p) else x[:, 0], cache
    )


def init_slot_state(model: CausalLM, *, max_batch: int, cache_len: int,
                    gen_cap: int, federated: bool, seed: int):
    """The pool: one padded cache + per-slot control vectors, all device-side.

    Cache layout is ``model.init_cache`` with the attention ``pos`` leaf
    broadcast from ``(nblocks, sc)`` to ``(nblocks, max_batch, sc)`` — each
    slot owns its positions, so slots at different prompt lengths / decode
    depths coexist in one program.  ``done`` starts all-True (empty slots);
    an empty slot keeps decoding garbage in lock step, which is harmless:
    its frozen ``tok``/``pos`` make the ring-buffer cache write idempotent
    and admission fully overwrites the slot's cache rows.
    """
    cache = model.init_cache(max_batch, cache_len)
    cache = jax.tree_util.tree_map_with_path(
        lambda p, x: (
            jnp.broadcast_to(x[:, None], (x.shape[0], max_batch, x.shape[1])).copy()
            if _is_pos_leaf(p) else x
        ),
        cache,
    )
    state = {
        "cache": cache,
        "tok": jnp.zeros((max_batch,), jnp.int32),
        "pos": jnp.zeros((max_batch,), jnp.int32),
        "done": jnp.ones((max_batch,), bool),
        "emitted": jnp.zeros((max_batch,), jnp.int32),
        "budget": jnp.zeros((max_batch,), jnp.int32),
        "eos": jnp.full((max_batch,), -1, jnp.int32),
        "out": jnp.zeros((max_batch, gen_cap), jnp.int32),
        "key": jax.random.split(jax.random.PRNGKey(seed), max_batch),
        "steps": jnp.zeros((), jnp.int32),
        "active_steps": jnp.zeros((), jnp.int32),
    }
    if federated:
        state["cluster"] = jnp.zeros((max_batch,), jnp.int32)
    return state


def build_slot_programs(model: CausalLM, *, temperature: float, gen_cap: int,
                        chunk_steps: int, stacked: bool):
    """Compile the three slot programs; returns ``(prefill, admit, chunk)``.

    ``stacked=True`` builds the federated variant: weights arrive as one
    ``(D, ...)`` cluster stack, each slot gathers its own cluster's tree
    inside the vmap (``state["cluster"]`` routes), and logits use the
    transposed einsum documented in the module docstring.
    """
    cfg = model.cfg
    if cfg.modality == "audio" and cfg.num_codebooks > 1:
        raise ValueError("continuous batching supports single-codebook token "
                         "streams; audio multi-codebook decode is not slotted")

    def _hidden1(params, tok, cache1, q_pos):
        x, nc = model.decode_hidden(params, tok[None], add_batch_dim(cache1), q_pos)
        return x[0], drop_batch_dim(nc)

    if stacked:
        def _slot_hidden(stack, d, tok, cache1, q_pos):
            p = jax.tree.map(lambda w: w[d], stack)
            return _hidden1(p, tok, cache1, q_pos)

        vhidden = jax.vmap(_slot_hidden, in_axes=(None, 0, 0, 1, 0), out_axes=(0, 1))

        def _logits(stack, x, d_vec):
            # Gathered per-slot output weights: the transposed layout keeps
            # the contraction bitwise-identical to the shared-weights einsum.
            if cfg.tie_embeddings:
                w = jnp.swapaxes(stack["embed"], -2, -1)[d_vec]  # (B, d, Vp)
            else:
                w = stack["head"][d_vec]                          # (B, d, Vp)
            out = jnp.einsum("bsd,bdv->bsv", x, w.astype(x.dtype))
            return softcap(out.astype(jnp.float32), cfg.final_logit_softcap)
    else:
        vhidden = jax.vmap(_hidden1, in_axes=(None, 0, 1, 0), out_axes=(0, 1))

        def _logits(params, x, d_vec):
            return model.logits(params, x)

    def _sample(keys, logits_last):
        flat = logits_last[..., : cfg.vocab_size]
        if temperature <= 0:
            return keys, jnp.argmax(flat, axis=-1).astype(jnp.int32)

        def one(k, row):
            k_next, k_draw = jax.random.split(k)
            return k_next, jax.random.categorical(k_draw, row / temperature)

        keys, tok = jax.vmap(one)(keys, flat)
        return keys, tok.astype(jnp.int32)

    # -- prefill (batch 1, one compile per bucket length) --------------------
    if stacked:
        def _prefill(weights, d, toks):
            p = jax.tree.map(lambda w: w[d], weights)
            return model.prefill(p, {"tokens": toks})
    else:
        def _prefill(weights, d, toks):
            return model.prefill(weights, {"tokens": toks})

    prefill = jax.jit(_prefill)

    # -- admit (constant-shape scatter into a traced slot index) -------------
    def _admit(state, row_cache, row_logits, slot, blen, eos, budget, key_row,
               cluster):
        def scatter(path, big, row):
            if _is_pos_leaf(path):
                pad = big.shape[-1] - row.shape[-1]
                row = jnp.pad(row, ((0, 0), (0, pad)), constant_values=-1)
                return big.at[:, slot].set(row)
            r = row[:, 0]  # drop the batch-1 axis
            pad = big.shape[2] - r.shape[1]
            if pad:
                r = jnp.pad(r, ((0, 0), (0, pad)) + ((0, 0),) * (r.ndim - 2))
            return big.at[:, slot].set(r.astype(big.dtype))

        cache = jax.tree_util.tree_map_with_path(scatter, state["cache"], row_cache)
        key_store, tok0 = _sample(key_row[None], row_logits[:, -1])
        new = {
            **state,
            "cache": cache,
            "tok": state["tok"].at[slot].set(tok0[0]),
            "pos": state["pos"].at[slot].set(blen),
            "done": state["done"].at[slot].set(False),
            "emitted": state["emitted"].at[slot].set(0),
            "budget": state["budget"].at[slot].set(budget),
            "eos": state["eos"].at[slot].set(eos),
            "out": state["out"].at[slot].set(jnp.zeros((gen_cap,), jnp.int32)),
            "key": state["key"].at[slot].set(key_store[0]),
        }
        if cluster is not None:
            new["cluster"] = state["cluster"].at[slot].set(cluster)
        return new

    admit = jax.jit(_admit, donate_argnums=0)

    # -- chunk (K decode steps, one compile ever) ----------------------------
    def _chunk(weights, state):
        max_batch = state["done"].shape[0]
        rows = jnp.arange(max_batch)

        def cond(carry):
            i, st = carry
            return (i < chunk_steps) & ~jnp.all(st["done"])

        def body(carry):
            i, st = carry
            tok, pos, done = st["tok"], st["pos"], st["done"]
            active = ~done
            # append: inactive rows index out of bounds and are dropped
            idx = jnp.where(active, jnp.minimum(st["emitted"], gen_cap - 1), gen_cap)
            out = st["out"].at[rows, idx].set(tok, mode="drop")
            emitted = st["emitted"] + active.astype(jnp.int32)
            done = done | (active & ((tok == st["eos"]) | (emitted >= st["budget"])))
            d_vec = st.get("cluster")
            if stacked:
                x, cache = vhidden(weights, d_vec, tok, st["cache"], pos)
            else:
                x, cache = vhidden(weights, tok, st["cache"], pos)
            logits = _logits(weights, x, d_vec)
            keys, new_tok = _sample(st["key"], logits[:, -1])
            still = ~done
            st = {
                **st,
                "cache": cache,
                # done/empty slots freeze tok+pos: the next step's ring write
                # then rewrites identical k/v at the same slot (idempotent)
                "tok": jnp.where(still, new_tok, tok),
                "pos": jnp.where(still, pos + 1, pos),
                "done": done,
                "emitted": emitted,
                "out": out,
                "key": keys,
                "steps": st["steps"] + 1,
                "active_steps": st["active_steps"] + active.sum().astype(jnp.int32),
            }
            return (i + 1, st)

        _, state = jax.lax.while_loop(cond, body, (jnp.int32(0), state))
        return state

    chunk = jax.jit(_chunk, donate_argnums=1)
    return prefill, admit, chunk


def compile_count(fn) -> int:
    """Number of distinct shapes a jitted program has compiled for."""
    return int(fn._cache_size())
