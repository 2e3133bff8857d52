"""Training driver: streaming-VQ retriever end-to-end (CPU-runnable).

``python -m repro.launch.train --steps 300 --batch 256`` trains the
paper's retriever on the synthetic impression + candidate streams with
the full production loop: multi-optimizer, EMA codebook, real-time
assignment write-back, periodic async checkpoints, auto-resume, and a
final retrieval-quality report against brute-force ground truth.

``--arch <id>`` instead trains one assigned architecture's reduced
(smoke) config for a few steps — the per-arch end-to-end driver.
"""
from __future__ import annotations

import argparse
import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.baselines import mips_topk, recall_at_k
from repro.configs import family, get_smoke
from repro.configs.base import SVQConfig
from repro.core import assignment_store as astore
from repro.core import retriever
from repro.data import RecsysStream, StreamConfig, lm_batch, \
    batched_molecules, random_geometric_graph
from repro.optim import adagrad, adamw, clip_by_global_norm, \
    multi_optimizer
from repro.serving import extract_deltas
from repro.train import LoopConfig, run_loop
from repro.utils.compile_cache import enable_compile_cache


def _route(path):
    return "adagrad" if "tables" in jax.tree_util.keystr(path) else "adamw"


def _svq_opt():
    return multi_optimizer(_route, {"adagrad": adagrad(0.05),
                                    "adamw": adamw(1e-3)})


def _svq_step_fn(cfg: SVQConfig, opt, use_kernel: bool = False):
    """The jitted SVQ train step shared by the offline and live loops."""
    @jax.jit
    def step_fn(state, batch):
        imp = {k: jnp.asarray(v) for k, v in batch["imp"].items()}
        cand = {k: jnp.asarray(v) for k, v in batch["cand"].items()}
        grads, new_index, metrics = retriever.train_step(
            state["params"], state["index"], cfg, imp, cand,
            use_kernel=use_kernel)
        grads, gn = clip_by_global_norm(grads, 10.0)
        params, opt_state = opt.update(grads, state["opt"],
                                       state["params"], state["step"])
        return ({"params": params, "index": new_index, "opt": opt_state,
                 "step": state["step"] + 1},
                dict(loss=metrics["loss"], grad_norm=gn,
                     used_clusters=metrics["used_clusters"],
                     perplexity=metrics["perplexity"]))

    return step_fn


def train_svq(cfg: SVQConfig, stream: RecsysStream, n_steps: int,
              batch: int, ckpt_dir: str | None = None,
              log_every: int = 0, seed: int = 0, use_kernel: bool = False,
              stats=None):
    """-> (params, index_state, loop_result).

    ``use_kernel`` runs the train step's Pallas kernels (VQ assignment,
    EMA segment sums, in-batch softmax); ``stats`` (a ServeStats)
    receives per-step ``train_step`` times."""
    opt = _svq_opt()
    params, index = retriever.init(jax.random.PRNGKey(seed), cfg)
    state = {"params": params, "index": index, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    step_fn = _svq_step_fn(cfg, opt, use_kernel)

    def batch_iter(step):
        return {"imp": stream.impression_batch(batch),
                "cand": stream.candidate_batch(batch)}

    loop_cfg = LoopConfig(n_steps=n_steps, ckpt_dir=ckpt_dir,
                          ckpt_every=max(n_steps // 4, 1),
                          log_every=log_every, sync_every=10, stats=stats)
    res = run_loop(step_fn, state, batch_iter, loop_cfg)
    return res.state["params"], res.state["index"], res


def train_svq_live(cfg: SVQConfig, stream: RecsysStream, service,
                   params, index_state, n_steps: int, batch: int,
                   immediate: bool = True, log_every: int = 0,
                   swap_model: bool = False, stats=None, registry=None):
    """Continue training WHILE publishing into a live RetrievalService.

    The streaming-production shape of §3.1: every train step's
    (re)assignment write-back is diffed against the previous step's
    store (``serving.extract_deltas``) from a ``LoopConfig.on_step``
    hook and pushed into ``service.apply_deltas`` —
    ``immediate=True`` edits the live index in place (spare-capacity
    path, forced compaction on overflow); ``immediate=False`` is the
    deferred baseline whose writes only become retrievable at the next
    rebuild.  ``index_state`` must be the state the service currently
    reflects (what it was constructed with / last swapped to), so the
    first step's diff base matches the serving side.

    ``swap_model=True`` additionally pushes the final params + state
    into the service (the §3.1 model-dump cadence, one dump).
    -> (params, index_state, loop_result).
    """
    opt = _svq_opt()
    state = {"params": params, "index": index_state,
             "opt": opt.init(params), "step": jnp.zeros((), jnp.int32)}
    step_fn = _svq_step_fn(cfg, opt)
    prev = {"store": index_state.store}

    def on_step(step, state, b):
        new_store = state["index"].store
        ids = np.concatenate([
            np.asarray(b["imp"]["item_id"]).ravel(),
            np.asarray(b["cand"]["item_id"]).ravel()])
        db = extract_deltas(prev["store"], new_store, jnp.asarray(ids))
        prev["store"] = new_store
        if db.n:
            service.apply_deltas(db, immediate=immediate)

    def batch_iter(step):
        return {"imp": stream.impression_batch(batch),
                "cand": stream.candidate_batch(batch)}

    loop_cfg = LoopConfig(n_steps=n_steps, log_every=log_every,
                          sync_every=10, on_step=on_step, stats=stats,
                          registry=registry)
    res = run_loop(step_fn, state, batch_iter, loop_cfg)
    if swap_model:
        service.swap_model(res.state["params"], res.state["index"])
    return res.state["params"], res.state["index"], res


def eval_svq_recall(cfg: SVQConfig, params, index_state,
                    stream: RecsysStream, n_users: int = 64,
                    k: int = 50) -> Dict[str, float]:
    """Recall@K of the VQ retrieval path vs ground-truth affinity."""
    idx = astore.build_serving_index(index_state.store, cfg.n_clusters)
    users = np.arange(n_users) % stream.cfg.n_users
    batch = dict(user_id=jnp.asarray(users, jnp.int32),
                 hist=jnp.asarray(stream.user_hist[users], jnp.int32))
    out = retriever.serve(params, index_state, cfg, idx, batch)
    got = np.asarray(out["item_ids"])[:, :k]
    truth = stream.true_topk(users, k)
    return dict(recall=recall_at_k(got, truth),
                served_valid=float(np.asarray(out["valid"]).mean()))


# ---------------------------------------------------------------------------
# Per-arch smoke training (reduced configs, CPU)
# ---------------------------------------------------------------------------

def train_arch_smoke(arch: str, n_steps: int = 5, batch: int = 8,
                     seed: int = 0) -> Dict[str, float]:
    cfg = get_smoke(arch)
    fam = family(arch)
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    if fam == "lm":
        from repro.models import lm as lm_lib
        from repro.models.lm import transformer as tfm
        params = tfm.init_lm(key, cfg)
        opt = adamw(1e-3)
        opt_state = opt.init(params)
        losses = []
        for step in range(n_steps):
            b = lm_batch(rng, batch, 32, cfg.vocab)
            (loss, _), grads = jax.value_and_grad(
                functools.partial(tfm.lm_loss, cfg=cfg,
                                  batch={k: jnp.asarray(v)
                                         for k, v in b.items()}),
                has_aux=True)(params)
            grads, _ = clip_by_global_norm(grads, 1.0)
            params, opt_state = opt.update(grads, opt_state, params,
                                           jnp.asarray(step))
            losses.append(float(loss))
        return dict(first_loss=losses[0], last_loss=losses[-1])
    if fam == "gnn":
        from repro.models.gnn import mace as mace_lib
        g = random_geometric_graph(rng, 64, 6, 8, cfg.n_classes)
        params = mace_lib.init_mace(key, cfg, 8, cfg.n_classes)
        opt = adamw(1e-3)
        opt_state = opt.init(params)
        b = {k: jnp.asarray(v) for k, v in g.items()}
        losses = []
        for step in range(n_steps):
            (loss, _), grads = jax.value_and_grad(
                lambda p: mace_lib.node_class_loss(p, cfg, b),
                has_aux=True)(params)
            grads, _ = clip_by_global_norm(grads, 10.0)
            params, opt_state = opt.update(grads, opt_state, params,
                                           jnp.asarray(step))
            losses.append(float(loss))
        return dict(first_loss=losses[0], last_loss=losses[-1])
    # recsys
    from repro.launch.bindings import _RECSYS_MODS
    mod = _RECSYS_MODS[cfg.kind]
    params = mod.init(key, cfg)
    opt = multi_optimizer(_route, {"adagrad": adagrad(0.05),
                                   "adamw": adamw(1e-3)})
    opt_state = opt.init(params)
    losses = []
    for step in range(n_steps):
        b = _smoke_recsys_batch(cfg, rng, batch)
        (loss, _), grads = jax.value_and_grad(
            lambda p: mod.loss(p, cfg, b), has_aux=True)(params)
        grads, _ = clip_by_global_norm(grads, 10.0)
        params, opt_state = opt.update(grads, opt_state, params,
                                       jnp.asarray(step))
        losses.append(float(loss))
    return dict(first_loss=losses[0], last_loss=losses[-1])


def _smoke_recsys_batch(cfg, rng, b):
    j = lambda x: jnp.asarray(x)
    if cfg.kind in ("din", "bst"):
        s = cfg.seq_len
        return dict(
            user_id=j(rng.integers(0, 500, b).astype(np.int32)),
            context=j(rng.integers(0, 16, b).astype(np.int32)),
            hist_items=j(rng.integers(0, 1000, (b, s)).astype(np.int32)),
            hist_cates=j(rng.integers(0, 50, (b, s)).astype(np.int32)),
            target_item=j(rng.integers(0, 1000, b).astype(np.int32)),
            target_cate=j(rng.integers(0, 50, b).astype(np.int32)),
            label=j((rng.random(b) > 0.5).astype(np.float32)))
    if cfg.kind == "dlrm":
        out = dict(dense=j(rng.normal(size=(b, cfg.n_dense))
                           .astype(np.float32)),
                   label=j((rng.random(b) > 0.5).astype(np.float32)))
        for t in cfg.tables:
            shp = (b, t.bag_size) if t.bag_size > 1 else (b,)
            out[t.name] = j(rng.integers(0, t.vocab, shp).astype(np.int32))
        return out
    bag = next(t.bag_size for t in cfg.tables if t.name == "user_hist")
    return dict(user_id=j(rng.integers(0, 500, b).astype(np.int32)),
                user_hist=j(rng.integers(0, 1000, (b, bag))
                            .astype(np.int32)),
                item_id=j(rng.integers(0, 1000, b).astype(np.int32)),
                item_cate=j(rng.integers(0, 50, b).astype(np.int32)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="svq")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=20)
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch == "svq":
        cfg = get_smoke("svq").with_(n_clusters=256, n_items=20000,
                                     n_users=5000, embed_dim=32,
                                     clusters_per_query=32,
                                     candidates_out=256)
        stream = RecsysStream(StreamConfig(n_items=cfg.n_items,
                                           n_users=cfg.n_users,
                                           hist_len=cfg.user_hist_len))
        params, index, res = train_svq(cfg, stream, args.steps,
                                       args.batch, args.ckpt_dir,
                                       args.log_every)
        rep = eval_svq_recall(cfg, params, index, stream)
        print(f"[train] final: {res.metrics[-1]}")
        print(f"[eval] recall@50 vs ground truth: {rep['recall']:.3f} "
              f"(served_valid={rep['served_valid']:.2f})")
    else:
        rep = train_arch_smoke(args.arch, n_steps=args.steps,
                               batch=args.batch)
        print(f"[train {args.arch}] {rep}")


if __name__ == "__main__":
    main()
