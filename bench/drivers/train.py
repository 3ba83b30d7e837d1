"""Driver of the train cells: ``repro.launch.train``'s Trainer, one
``Trainer.run(1)`` per unit of work, which ends on the step's loss on the
host.

Set-up builds the trainer for the configuration (checking that the program
runs the model the file states), makes the weights from the seed on the
device in one jitted call in their sharded layout with a fresh AdamW state,
seeds the synthetic token stream, and drives the trainer through its first
three steps: the first compiles.  Those steps go through the window's own
call and feed; their losses, the per-leaf norms of the first step's clipped
gradient (read back from AdamW's first moment) and of the parameters'
change over the three steps are kept.  ``verify`` frees the program's state
and runs the plain reference (``bench/reference/qwen3.py``) over the same
three batches from the same weights.
"""
from __future__ import annotations

import gc
import math

import numpy as np

CHECKED_STEPS = 3


def model_config(cfg: dict):
    """The program's ModelConfig for the configuration file: the program's
    own model of that family with every size the file states, or an error
    naming where its structure departs from the file."""
    from repro.configs import get_config
    mc = get_config(cfg["program_arch"]).replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        vocab_size=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"])
    want = {"tie_embeddings": cfg["tie_word_embeddings"],
            "qkv_bias": cfg["attention_bias"], "activation": cfg["hidden_act"],
            "gated_mlp": True, "qk_norm": True, "norm": "rmsnorm",
            "pos_embedding": "rope", "window": 0, "logit_softcap": 0.0,
            "moe": None, "family": "dense",
            "param_dtype": cfg["param_dtype"],
            "compute_dtype": cfg["compute_dtype"]}
    bad = [f"{k}: program {getattr(mc, k)!r}, configuration {v!r}"
           for k, v in want.items() if getattr(mc, k) != v]
    if bad:
        raise ValueError("the program's model is not the configuration: "
                         + "; ".join(bad))
    return mc


def rel_gap(a: float, b: float) -> float:
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-300)


def worst_leaf(prog: dict, ref: dict, keep=None) -> float:
    """The widest gap between the program's and the reference's norm of a
    leaf, over the larger of that leaf's and the median leaf's norm."""
    keys = [k for k in ref if keep is None or keep(k)]
    med = float(np.median([ref[k] for k in ref]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def compare(prog: dict, ref: dict) -> list:
    """The compared numbers, from (losses, g1, delta) of both sides.
    Leaves whose first gradient is nought to rounding in the reference
    (under a thousandth of the median leaf's) move under AdamW by round-off
    alone and are left out of the parameters' change."""
    med_g = float(np.median(list(ref["g1"].values())))
    moved = [k for k, g in ref["g1"].items() if g >= 1e-3 * med_g]
    return [
        {"name": "loss_rel",
         "value": max(rel_gap(a, b)
                      for a, b in zip(prog["losses"], ref["losses"]))},
        {"name": "grad1_leaf_rel",
         "value": worst_leaf(prog["g1"], ref["g1"])},
        {"name": "delta3_leaf_rel",
         "value": worst_leaf(prog["delta"], ref["delta"],
                             keep=lambda k: k in moved)},
    ]


def flat(tree, is_leaf=None) -> dict:
    """``path -> leaf`` of a nested dict, paths joined by ``/``."""
    import jax
    leaves = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): x
            for path, x in leaves}


class Driver:
    span = "train"

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.devices = devices
        self.trainer = None
        self.readings = None            # the program's (losses, g1, delta)
        self.batches = []

    def build(self):
        """The trainer for this cell, before any state exists."""
        from jax.sharding import Mesh
        from repro.launch.train import build_parser, build_trainer
        from repro.train.data import DataConfig, SyntheticTokens

        t = self.traffic
        mc = model_config(self.cfg)
        args = build_parser().parse_args([
            "--arch", self.cfg["program_arch"],
            "--steps", str(t["schedule_steps"]),
            "--global-batch", str(t["global_batch"]),
            "--seq-len", str(t["seq_len"]), "--lr", str(t["lr"]),
            "--use-case", t["use_case"], "--checkpoint-every", "0",
            "--checkpoint-dir", "bench/out/checkpoints"])
        mesh = Mesh(np.array(self.devices).reshape(t["mesh"]),
                    ("data", "model"))
        trainer = build_trainer(args, model_cfg=mc, mesh=mesh)
        tc = trainer.cfg.train
        have = {"warmup_steps": tc.warmup_steps, "min_lr_frac": tc.min_lr_frac,
                "beta1": tc.beta1, "beta2": tc.beta2, "eps": tc.eps,
                "weight_decay": tc.weight_decay, "grad_clip": tc.grad_clip,
                "z_loss_weight": getattr(trainer.model, "z_loss_weight", 1e-4),
                "remat": trainer.cfg.parallel.remat_policy}
        bad = [f"{k}: program {v!r}, traffic {t[k]!r}"
               for k, v in have.items() if v != t[k]]
        if bad:
            raise ValueError("the program's training is not the traffic's: "
                             + "; ".join(bad))
        trainer.data = SyntheticTokens(
            DataConfig(global_batch=t["global_batch"], seq_len=t["seq_len"],
                       seed=self.seed), mc)
        return trainer

    def init_state(self, trainer) -> None:
        """Weights from the seed, made on the device in one jitted call in
        the program's sharded layout, with a fresh optimizer state."""
        import jax
        import jax.numpy as jnp
        from bench.reference.qwen3 import init_params, param_shapes, seed_key
        from repro.parallel.fsdp import TrainState
        from repro.train.optimizer import AdamWState

        spec = trainer.model.param_specs()
        is_spec = lambda x: hasattr(x, "axes")          # noqa: E731
        like = {k: tuple(s.shape) for k, s in flat(spec, is_spec).items()}
        if like != param_shapes(self.cfg):
            raise ValueError(f"the program's parameters {like} are not the "
                             f"configuration's {param_shapes(self.cfg)}")
        treedef = jax.tree_util.tree_structure(spec, is_leaf=is_spec)
        order = list(like)
        cfg = self.cfg

        def make(key):
            p = init_params(cfg, key)
            params = jax.tree_util.tree_unflatten(treedef,
                                                  [p[k] for k in order])
            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
            return TrainState(params, AdamWState(
                jnp.zeros((), jnp.int32), zeros,
                jax.tree_util.tree_map(jnp.zeros_like, params)), None)

        trainer.state = jax.jit(make, out_shardings=trainer.state_shardings)(
            seed_key(self.seed))
        trainer.step = 0

    def program_readings(self, trainer) -> dict:
        """Drive the first steps and read losses, the first clipped
        gradient and the parameters' change, per leaf."""
        import jax
        from bench.reference.qwen3 import init_leaf, leaf_norms, seed_key
        b1 = trainer.cfg.train.beta1
        cfg = self.cfg
        g_norms = jax.jit(lambda m: leaf_norms(
            {k: x / (1 - b1) for k, x in flat(m).items()}))
        d_norms = jax.jit(lambda p, key: leaf_norms(
            {k: x - init_leaf(cfg, key, k, x.shape)
             for k, x in flat(p).items()}))
        self.batches = []
        g1 = None
        for i in range(CHECKED_STEPS):
            self.batches.append(trainer.data.batch_at(trainer.step))
            self.step(i - CHECKED_STEPS)
            if i == 0:
                g1 = {k: float(x) for k, x in
                      g_norms(trainer.state.opt.exp_avg).items()}
        delta = {k: float(x) for k, x in
                 d_norms(trainer.state.params, seed_key(self.seed)).items()}
        losses = [m["loss"] for m in trainer.metrics_log[:CHECKED_STEPS]]
        return {"losses": losses, "g1": g1, "delta": delta}

    def setup(self) -> None:
        self.trainer = self.build()
        self.init_state(self.trainer)
        self.readings = self.program_readings(self.trainer)

    def step(self, k: int) -> dict:
        n = len(self.trainer.metrics_log)
        self.trainer.run(1)
        log = self.trainer.metrics_log
        ok = len(log) == n + 1 and math.isfinite(log[-1]["loss"])
        tokens = self.traffic["global_batch"] * self.traffic["seq_len"]
        return {"tokens": tokens, "failed": not ok}

    def reference(self, precision: str = "float32") -> dict:
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from bench.reference.qwen3 import reference_readings

        shard = None
        n = len(self.devices)
        if n > 1:
            mesh = Mesh(np.array(self.devices), ("x",))

            def shard(path, shape):
                dims = [i for i, s in enumerate(shape) if s % n == 0]
                spec = [None] * len(shape)
                if dims:
                    spec[max(dims, key=lambda i: shape[i])] = "x"
                return NamedSharding(mesh, P(*spec))
        with jax.default_device(self.devices[0]):
            losses, g1, delta = reference_readings(
                self.cfg, self.traffic, self.seed, self.batches,
                precision=precision, chunks=self.traffic["head_chunks"],
                shard=shard)
        return {"losses": losses, "g1": g1, "delta": delta}

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        import jax
        if self.trainer is not None:
            self.trainer.state = None
            self.trainer = None
        gc.collect()
        jax.clear_caches()

    def verify(self) -> list:
        self.release()
        return compare(self.readings, self.reference("float32"))
