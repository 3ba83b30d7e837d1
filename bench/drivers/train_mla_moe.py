"""Driver of the latent-attention expert-layer train cells (DeepSeek-V2):
the train driver (``bench/drivers/train.py``) for a configuration file in
DeepSeek-V2's keys, checked against ``bench/reference/deepseek_v2.py``.

Set-up and the checked steps are the train driver's: the trainer built for
the configuration, weights from the seed on the device, the first three
steps through the window's own call and feed, then ``Trainer.run(1)`` per
unit of work.  Besides, set-up reads from the compiled step (the same
executable, from the compile cache) which scope each of its instructions
runs under (``bench/scopes.py``); every unit's record carries that map and
the step's expert-layer counter, ``moe_rows_held``, for the per-layer
readers.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.drivers.train import Driver as TrainDriver
from bench.drivers.train import flat
from bench.scopes import SCOPES, scope_map


def model_config(cfg: dict):
    """The program's ModelConfig for the configuration file: the program's
    own model of that family with every size the file states, holding the
    file's routed experts, or an error naming where its structure departs
    from the file."""
    from repro.configs import MLAConfig, YarnConfig, get_config
    mc = get_config(cfg["program_arch"])
    y = cfg["rope_scaling"]
    first = cfg["first_held_expert"]
    mc = mc.replace(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        max_seq_len=cfg["max_position_embeddings"],
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      nope_dim=cfg["qk_nope_head_dim"],
                      rope_dim=cfg["qk_rope_head_dim"],
                      v_dim=cfg["v_head_dim"]),
        yarn=YarnConfig(factor=float(y["factor"]),
                        original_max_len=y["original_max_position_embeddings"],
                        beta_fast=float(y["beta_fast"]),
                        beta_slow=float(y["beta_slow"]),
                        mscale=y["mscale"], mscale_all_dim=y["mscale_all_dim"]),
        moe=dataclasses.replace(
            mc.moe, n_experts=cfg["published"]["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"],
            d_expert=cfg["moe_intermediate_size"],
            n_shared=cfg["n_shared_experts"],
            first_k_dense=cfg["first_k_dense_replace"],
            d_ff_dense=cfg["intermediate_size"],
            norm_topk=cfg["norm_topk_prob"],
            aux_loss_weight=cfg["aux_loss_alpha"],
            held=(first, cfg["n_routed_experts"])))
    want = {"tie_embeddings": cfg["tie_word_embeddings"],
            "qkv_bias": cfg["attention_bias"], "activation": cfg["hidden_act"],
            "gated_mlp": True, "qk_norm": False, "norm": "rmsnorm",
            "pos_embedding": "rope", "window": 0, "logit_softcap": 0.0,
            "family": "moe", "param_dtype": cfg["param_dtype"],
            "compute_dtype": cfg["compute_dtype"]}
    bad = [f"{k}: program {getattr(mc, k)!r}, configuration {v!r}"
           for k, v in want.items() if getattr(mc, k) != v]
    moe_want = {"router": cfg["scoring_func"], "capacity_factor": None}
    bad += [f"moe.{k}: program {getattr(mc.moe, k)!r}, configuration {v!r}"
            for k, v in moe_want.items() if getattr(mc.moe, k) != v]
    if (cfg["q_lora_rank"] is not None or cfg["moe_layer_freq"] != 1
            or cfg["routed_scaling_factor"] != 1):
        bad.append("the configuration has a query low-rank, expert layers "
                   "at intervals or a routed scale other than 1, which the "
                   "program does not build")
    if bad:
        raise ValueError("the program's model is not the configuration: "
                         + "; ".join(bad))
    return mc


class Driver(TrainDriver):
    span = "train"

    def __init__(self, config: dict, traffic: dict, seed: int, devices):
        super().__init__(config, traffic, seed, devices)
        self.scopes = {}

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
        from bench.reference.deepseek_v2 import init_params, param_shapes
        from bench.reference.qwen3 import seed_key
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

    def setup(self) -> None:
        import jax
        super().setup()
        t = self.trainer
        batch = {k: jax.numpy.asarray(v)
                 for k, v in t.data.batch_at(t.step).items()}
        with t.mesh:
            hlo = t.step_fn.lower(t.state, batch).compile().as_text()
        self.scopes = scope_map(hlo, SCOPES)

    def step(self, k: int) -> dict:
        work = super().step(k)
        work["scopes"] = self.scopes
        if not work["failed"]:
            log = self.trainer.metrics_log[-1]
            work["moe_rows_held"] = log["moe_rows_held"]
        return work

    def reference(self, precision: str = "float32") -> dict:
        import jax
        from bench.reference.deepseek_v2 import reference_readings

        with jax.default_device(self.devices[0]):
            losses, g1, delta = reference_readings(
                self.cfg, self.traffic, self.seed, self.batches,
                precision=precision, chunks=self.traffic["head_chunks"])
        return {"losses": losses, "g1": g1, "delta": delta}
