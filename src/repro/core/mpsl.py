"""MPSL train step — the paper's technique as one SPMD program.

One jitted step realizes the full client/server exchange:

  1. client forward  — per-client heads (stacked [N, ...] params, vmapped
     math) tokenize local minibatches into smashed data a_n;
  2. uplink          — activations resharded from the client axis into the
     server's global-batch layout (the paper's server-side concat; int8-
     compressed when enabled);
  3. server forward  — ONE unified encoder pass over the concatenated
     global batch (frozen prefix + trainable suffix), no per-client
     sub-models;
  4. tail + losses   — predictions return to the client layout, each
     client computes its own loss against labels that never left its
     shard (no label sharing); per-client losses L_n are combined as
     L_S = sum_n |B_n|/|B| * L_n with a participation mask (straggler /
     dropout handling);
  5. single backward — jax.grad of L_S IS the paper's single aggregated
     backward pass; cut-layer gradients reach each client's adapter
     through the same program (int8-compressed when enabled).

`backward_mode='per_client'` provides the vanilla-PSL baseline (N separate
backward passes via lax.map) for the cost comparison benchmarks.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import compression, fusion, losses, split
from repro.models import layers, model as M, tokenizers as tok
from repro.obs import comm as obs_comm
from repro.obs import recorder as _rec
from repro.optim import (adamw_init, adamw_update, apply_updates,
                         clip_by_global_norm)
from repro.parallel import sharding


# ---------------------------------------------------------------------------
# Shared pieces


def _client_weights(mask, n):
    """w_n = |B_n| / |B| over participating clients (uniform B_n here)."""
    m = mask.astype(jnp.float32)
    return m / jnp.maximum(jnp.sum(m), 1.0)


def _account_links(h, mpsl, suffix: str = ""):
    """Trace-time per-link byte accounting of the client/server exchange.

    ``h`` is the stacked [N, Bn, ...] smashed-data array at the cut
    layer — its runtime shape/dtype IS the uplink payload, and (by the
    symmetry of the cut) the cut-layer-gradient downlink moves the same
    geometry. Runs while the step is traced; adds nothing to the jitted
    program (telemetry neutrality, asserted in tests)."""
    wire = (compression.compressed_bytes(h.shape[1:])
            if mpsl.compress_uplink else None)
    obs_comm.record_link("uplink.activations" + suffix, h.shape, h.dtype,
                         direction="uplink",
                         compressed=mpsl.compress_uplink,
                         wire_bytes_per_client=wire)
    wire = (compression.compressed_bytes(h.shape[1:])
            if mpsl.compress_downlink else None)
    obs_comm.record_link("downlink.gradients" + suffix, h.shape, h.dtype,
                         direction="downlink",
                         compressed=mpsl.compress_downlink,
                         wire_bytes_per_client=wire)


def _run_body(frozen, server, cfg, h, positions, enc_out=None):
    """Frozen prefix + trainable suffix, then final norm."""
    aux = jnp.zeros((), jnp.float32)
    fsegs, tsegs = _segments_for(frozen, server, cfg)
    with jax.named_scope("frozen_trunk"):
        for sp, seg in zip(frozen["segments"], fsegs):
            h, _, a = M.apply_segment(sp, h, cfg, seg, positions=positions,
                                      enc_out=enc_out)
            aux = aux + a
    with jax.named_scope("trainable_trunk"):
        for sp, seg in zip(server["segments"], tsegs):
            h, _, a = M.apply_segment(sp, h, cfg, seg, positions=positions,
                                      enc_out=enc_out)
            aux = aux + a
    h = layers.apply_norm(h, server["final_norm"], cfg.norm)
    return h, aux


def len_from_params(tree) -> int:
    return sum(M.stacked_layers(sp) for sp in tree["segments"])


def _segments_for(frozen, server, cfg):
    boundary = len_from_params(frozen)
    return split.split_segments(M.body_segments(cfg), boundary)


# ---------------------------------------------------------------------------
# LM-family MPSL loss (assigned architectures)


def make_lm_loss(cfg, run):
    """Returns loss_fn(trainable, frozen, batch, rng) -> (L_S, metrics).

    batch: tokens [N, Bn, S], labels [N, Bn, S], mask [N]
           (+ patch_embeds [N, Bn, P, D] for vlm,
            frame_embeds [N, Bn, F, D] for audio)."""
    mpsl = run.mpsl
    cdt = jnp.dtype(run.compute_dtype)
    owners = M.kv_share_pairs(cfg)

    def loss_fn(trainable, frozen, batch, rng):
        if owners:
            _rec.get().event("hybrid/kv_share",
                             layers=[i + 1 for i in owners],
                             from_layers=owners)
        tokens = batch["tokens"]
        n, bn, s_text = tokens.shape
        r_up, r_down = jax.random.split(jax.random.fold_in(rng, 1))

        # ---- 1. client forward: frozen tokenizer + per-client adapter ----
        with jax.named_scope("client_head"):
            h = frozen["embed"]["table"].astype(cdt)[tokens]   # [N,Bn,S,D]
            if cfg.pos_embed == "learned":
                h = h + frozen["embed"]["pos"].astype(cdt)[
                    layers.positions_from_shape(1, s_text)[0]]
            parts = [h]
            if "patch_embeds" in batch:
                parts = [batch["patch_embeds"].astype(cdt), h]
            h = jnp.concatenate(parts, axis=2) if len(parts) > 1 else h
            h = split.apply_client_adapter(trainable["client"]["adapter"], h)
            h = sharding.shard_act(h, ("client", None, None, None))

        # ---- 2. uplink (smashed data) ----
        _account_links(h, mpsl)
        if mpsl.compress_uplink:
            h = compression.compress_activations(h, r_up)
        if mpsl.compress_downlink:
            h = compression.compress_gradients(h, r_down)

        seq = h.shape[2]
        hb = h.reshape(n * bn, seq, cfg.d_model)
        if cfg.meta_tokens:
            # Hymba's meta tokens R (server-side, frozen): X~ = [R; X]
            meta = frozen["meta_tokens"].astype(hb.dtype)
            hb = jnp.concatenate(
                [jnp.broadcast_to(meta[None], (n * bn,) + meta.shape), hb],
                axis=1)
            seq = hb.shape[1]
        hb = sharding.shard_act(hb, ("batch", None, None))
        positions = _build_positions(cfg, batch, n * bn, seq)

        # ---- whisper: frozen encoder over stub frame embeddings ----
        enc_out = None
        if "frame_embeds" in batch:
            fe = batch["frame_embeds"].astype(cdt)
            fe = split.apply_client_adapter(trainable["client"]["adapter"], fe)
            fe = fe.reshape(n * bn, fe.shape[2], cfg.d_model)
            enc_out = M.run_encoder(frozen, fe, cfg)

        # ---- 3. server forward: ONE pass over the global batch ----
        hb, aux = _run_body(frozen, trainable["server"], cfg, hb, positions,
                            enc_out=enc_out)

        # ---- 4. tail in CLIENT layout: labels never leave their shard ----
        hc = hb.reshape(n, bn, seq, cfg.d_model)
        hc = sharding.shard_act(hc, ("client", None, None, None))
        # next-token LM loss on the text region only
        text0 = seq - s_text
        hc_text = hc[:, :, text0:, :]
        labels = batch["labels"]                                # [N,Bn,S]
        flat_h = hc_text[:, :, :-1, :].reshape(-1, cfg.d_model)
        flat_l = labels[:, :, 1:].reshape(-1)
        w_tail = (trainable["server"]["lm_head"] if "lm_head"
                  in trainable["server"] else
                  frozen["embed"]["table"].T)
        per_tok = losses.chunked_softmax_xent(flat_h, w_tail, flat_l)
        per_client = per_tok.reshape(n, -1).mean(axis=1)        # L_n

        # ---- 5. aggregated loss => single backward pass ----
        w = _client_weights(batch["mask"], n)
        l_s = jnp.sum(w * per_client) + aux
        metrics = {"loss": l_s, "per_client": per_client,
                   "aux": aux, "participating": jnp.sum(batch["mask"])}
        return l_s, metrics

    return loss_fn


def _build_positions(cfg, batch, b, seq):
    if cfg.pos_embed == "mrope" and "patch_embeds" in batch:
        p = batch["patch_embeds"].shape[2]
        grid = int(p ** 0.5) or 1
        idx = jnp.arange(p, dtype=jnp.int32)
        img = jnp.stack([jnp.zeros((p,), jnp.int32), idx // grid, idx % grid])
        t0 = (idx // grid).max() + 1 if p else 0
        tpos = jnp.arange(seq - p, dtype=jnp.int32) + t0
        txt = jnp.stack([tpos, tpos, tpos])
        pos3 = jnp.concatenate([img, txt], axis=1)              # [3, S]
        return jnp.broadcast_to(pos3[None], (b, 3, seq))
    return layers.positions_from_shape(b, seq)


# ---------------------------------------------------------------------------
# Paper-mode (ViT / Meta-Transformer) MPSL loss


def make_vit_loss(cfg, run, modalities=("vision", "text"),
                  task: str = "classification", n_classes: int = 10):
    mpsl = run.mpsl
    cdt = jnp.dtype(run.compute_dtype)

    def encode(frozen, server, tokens_bnd):
        b = tokens_bnd.shape[0]
        positions = layers.positions_from_shape(b, tokens_bnd.shape[1])
        return _run_body(frozen, server, cfg, tokens_bnd, positions)

    def loss_fn(trainable, frozen, batch, rng):
        mask = batch["mask"]
        n = mask.shape[0]
        r_up, r_down = jax.random.split(jax.random.fold_in(rng, 2))

        # ---- client tokenizers (per-client params, vmapped) ----
        tokenized = {}
        with jax.named_scope("client_head"):
            for m in modalities:
                spec = tok.MODALITIES[m]
                x = batch[m]
                f = functools.partial(tok.apply_tokenizer, spec=spec,
                                      dtype=cdt)
                tokenized[m] = jax.vmap(lambda p, xx: f(p, xx))(
                    trainable["client"]["tokenizers"][m], x)
                tokenized[m] = sharding.shard_act(
                    tokenized[m], ("client", None, None, None))

        bn = next(iter(tokenized.values())).shape[1]

        def uplink(a, link):
            _account_links(a, mpsl, suffix="/" + link)
            if mpsl.compress_uplink:
                a = compression.compress_activations(a, r_up)
            if mpsl.compress_downlink:
                a = compression.compress_gradients(a, r_down)
            return a.reshape((n * bn,) + a.shape[2:])

        aux = jnp.zeros((), jnp.float32)
        if task == "retrieval":
            enc = {}
            for m in modalities:
                e, a = encode(frozen, trainable["server"],
                              uplink(tokenized[m], m))
                enc[m] = e
                aux = aux + a
            ma, mb = sorted(modalities)
            emb_a = fusion.gap(fusion.summarize_modality(ma, enc[ma]))
            emb_b = fusion.gap(fusion.summarize_modality(mb, enc[mb]))
            pa = emb_a @ trainable["server"]["proj_a"].astype(cdt)
            pb = emb_b @ trainable["server"]["proj_b"].astype(cdt)
            temp = 1.0 / jnp.exp(trainable["server"]["logit_scale"])
            per_sample = losses.contrastive_loss(pa, pb, temp)   # [N*Bn]
            per_client = per_sample.reshape(n, bn).mean(axis=1)
        else:
            if mpsl.fusion == "early":
                joint = fusion.fuse_early(tokenized)             # [N,Bn,T,D]
                h, aux = encode(frozen, trainable["server"],
                                uplink(joint, "joint"))
                emb = fusion.gap(h)                              # [N*Bn, D]
            else:
                enc = {}
                for m in modalities:
                    e, a = encode(frozen, trainable["server"],
                                  uplink(tokenized[m], m))
                    enc[m] = e
                    aux = aux + a
                emb = fusion.gap(fusion.fuse_late(enc))
            th = trainable["server"]["task_head"]
            logits = emb @ th["w"].astype(cdt) + th["b"].astype(cdt)
            labels = batch["labels"].reshape(-1)
            per_sample = losses.softmax_xent(logits, labels)
            per_client = per_sample.reshape(n, bn).mean(axis=1)

        w = _client_weights(mask, n)
        l_s = jnp.sum(w * per_client) + aux
        metrics = {"loss": l_s, "per_client": per_client, "aux": aux,
                   "participating": jnp.sum(mask)}
        return l_s, metrics

    return loss_fn


# ---------------------------------------------------------------------------
# Train step factory


def _split_microbatches(batch, mu: int):
    """[N, Bn, ...] client batches -> [mu, N, Bn/mu, ...] microbatches.

    The client axis is preserved (it is the mesh's data axis); each
    client's LOCAL minibatch is what gets split — the paper's sequential
    large-batch simulation, noted in Sec. 4.2."""
    def f(k, x):
        if k == "mask":
            return jnp.broadcast_to(x[None], (mu,) + x.shape)
        n, bn = x.shape[:2]
        assert bn % mu == 0, (k, x.shape, mu)
        y = x.reshape((n, mu, bn // mu) + x.shape[2:])
        return jnp.swapaxes(y, 0, 1)
    return {k: f(k, v) for k, v in batch.items()}


def make_train_step(loss_fn, run, sched, backward_mode: str = "aggregated",
                    microbatches: int = 1, guard_nonfinite: bool = False):
    """One MPSL optimization step (client + server updates).

    aggregated  — the paper's single backward pass over L_S.
    per_client  — vanilla-PSL baseline: N separate backward passes
                  (lax.map over clients), summed. Gradients are identical
                  (linearity); cost is not — used by the benchmarks.

    guard_nonfinite — opt-in robustness (chaos runs / --fault-plan): when
    the aggregated loss or the clipped grad norm is non-finite, the step
    keeps params and BOTH Adam moments (incl. the count) bitwise
    unchanged via a traced select — donated-state-safe (the select reads
    the donated input buffers, no host roundtrip, no extra dispatch) and
    sync-free. The step counter still advances so the step-indexed
    loader/rng schedule stays aligned with the loop index (restart
    invariance). ``metrics["skipped"]`` carries the flag to the host at
    the normal readback cadence. Default False: the traced program is
    identical to a guard-free build (telemetry/fault neutrality)."""

    def grad_agg(params, frozen, batch, rng):
        if microbatches <= 1:
            return jax.value_and_grad(loss_fn, has_aux=True)(
                params, frozen, batch, rng)
        mb = _split_microbatches(batch, microbatches)

        def body(carry, b):
            g_acc, l_acc = carry
            (l, met), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, frozen, b, rng)
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
            return (g_acc, l_acc + l), met

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss_sum), mets = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32)), mb)
        inv = 1.0 / microbatches
        grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
        metrics = jax.tree_util.tree_map(lambda m: jnp.mean(m, axis=0), mets)
        return (loss_sum * inv, metrics), grads

    def step(state, batch):
        rng = jax.random.fold_in(state["rng"], state["step"])
        if backward_mode == "aggregated":
            (loss, metrics), grads = grad_agg(
                state["params"], state["frozen"], batch, rng)
        else:
            grads, loss, metrics = _per_client_grads(
                loss_fn, state["params"], state["frozen"], batch, rng)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, run.grad_clip)
            lr = sched(state["step"])
            updates, opt = adamw_update(
                grads, state["opt"], state["params"], lr=lr,
                weight_decay=run.weight_decay)
            params = apply_updates(state["params"], updates)
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        if guard_nonfinite:
            ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)

            def keep(new, old):
                return jnp.where(ok, new, old.astype(new.dtype))

            params = jax.tree_util.tree_map(keep, params, state["params"])
            opt = jax.tree_util.tree_map(keep, opt, state["opt"])
            okf = ok.astype(jnp.float32)
            metrics["skipped"] = 1.0 - okf
            # a skipped round contributed nothing; sanitize the fields
            # the host coerces at log boundaries
            metrics["participating"] = jnp.where(
                jnp.isfinite(metrics["participating"]),
                metrics["participating"], 0.0) * okf
        new_state = {"params": params, "frozen": state["frozen"],
                     "opt": opt, "step": state["step"] + 1,
                     "rng": state["rng"]}
        return new_state, metrics

    return step


def _per_client_grads(loss_fn, params, frozen, batch, rng):
    """Vanilla PSL: one backward per client (cost baseline).

    Each client's backward computes grad of its own L_n; the server then
    combines with the same global weights w_n = |B_n|/|B| the aggregated
    mode uses, so gradients are bitwise-comparable."""
    n = batch["mask"].shape[0]
    w = _client_weights(batch["mask"], n)

    def one(i):
        m = jax.nn.one_hot(i, n) * batch["mask"]
        b = dict(batch, mask=m)
        (l, met), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, frozen, b, rng)
        g = jax.tree_util.tree_map(lambda x: x * w[i], g)
        return g, l

    idx = jnp.arange(n)
    grads, ls = jax.lax.map(one, idx)
    grads = jax.tree_util.tree_map(lambda g: jnp.sum(g, axis=0), grads)
    loss = jnp.sum(w * ls)
    return grads, loss, {"loss": loss,
                         "per_client": ls,
                         "aux": jnp.zeros((), jnp.float32),
                         "participating": jnp.sum(batch["mask"])}


def init_state(params, frozen, seed: int = 0):
    return {
        "params": params,
        "frozen": frozen,
        "opt": adamw_init(params),
        "step": jnp.zeros((), jnp.int32),
        "rng": jax.random.PRNGKey(seed),
    }


# ---------------------------------------------------------------------------
# Jitted step: donation + placement


def state_shardings(state, mesh):
    """NamedShardings mirroring a train-step state: params/frozen/opt follow
    the path-based param rules (opt moments mirror their params —
    adamw_init zeros share shapes, so the same rule table resolves them);
    step counter and rng replicate."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    return {
        "params": sharding.param_shardings(state["params"], mesh),
        "frozen": sharding.param_shardings(state["frozen"], mesh),
        "opt": {"mu": sharding.param_shardings(state["opt"]["mu"], mesh),
                "nu": sharding.param_shardings(state["opt"]["nu"], mesh),
                "count": rep},
        "step": rep,
        "rng": rep,
    }


def place_state(state, mesh=None):
    """Commit a train-step state onto the mesh (or default device). A
    committed input fixes the jitted step's input shardings, which is what
    lets donation alias the output buffers exactly."""
    if mesh is None:
        return jax.tree_util.tree_map(jax.device_put, state)
    sh = state_shardings(state, mesh)
    return jax.tree_util.tree_map(jax.device_put, state, sh)


def jit_train_step(step_fn, donate: bool = True):
    """jit the train step with the state argument donated: params and
    optimizer moments alias in place of double-allocating (2x param+opt
    peak memory otherwise). The caller must drop its reference to the old
    state each step — the Trainer's `state, metrics = step(state, batch)`
    does; a second call on a donated handle raises."""
    return jax.jit(step_fn, donate_argnums=(0,) if donate else ())
