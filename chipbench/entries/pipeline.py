"""Entry ``pipeline``: the window drives the compiled step of
``repro.train.step.make_pipeline_train_step`` in the benchmark's own loop,
one step after another, as a trainer would: make the batch on the host,
place it, run the step, wait for the state.

Stages run over the ``pod`` axis of a (pod, data, model) mesh, with tensor
parallelism over ``model`` inside each stage.  The state's shardings are
the step's ``out_shardings`` too, and the state is donated.  The weights
are the benchmark's, made from the seed in one jitted call that also stages
them (the program's own ``build_staging``) and places each shard on its
device.
"""
from __future__ import annotations

import time
from typing import Dict

import importlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench.lib import traffic as traffic_lib


class Entry:
    def __init__(self, config: dict, traffic: dict, devices):
        from repro.configs.base import ArchConfig
        from repro.launch.mesh import make_mesh
        from repro.parallel.staging import build_staging
        from repro.train.optimizer import OptimizerConfig
        from repro.train.step import (
            make_pipeline_train_step, pipeline_state_shardings,
        )

        ref = importlib.import_module(
            f"chipbench.references.{config['reference']}")
        self.ref = ref
        self.arch, self.opt = config["arch"], config["optimizer"]
        self.cfg = ArchConfig(**self.arch)
        self.batch, self.seq = traffic["batch"], traffic["seq_len"]
        self.tokens_per_step = self.batch * self.seq
        shape = config["mesh"]
        self.n_stages = shape["pod"]
        n = shape["pod"] * shape["data"] * shape["model"]
        self.mesh = make_mesh((shape["pod"], shape["data"], shape["model"]),
                              ("pod", "data", "model"), devices=devices[:n])
        act = jnp.dtype(config["precision"]["activations"])
        opt = OptimizerConfig(**{k: v for k, v in self.opt.items()
                                 if k != "no_decay"})
        step, staging, opt_init, sh = make_pipeline_train_step(
            self.cfg, opt, mesh=self.mesh, n_stages=self.n_stages,
            n_microbatches=traffic["microbatches"], abstract=True,
            act_dtype=act)
        opt_struct = jax.eval_shape(
            opt_init, {"staged": staging.staged, "shared": staging.shared})
        staged_sh, shared_sh, opt_sh = pipeline_state_shardings(
            self.mesh, sh, staging, opt_struct)
        self.consts = jax.device_put(staging.consts, sh["consts"])
        self.batch_sh = NamedSharding(self.mesh, P("data", None))

        def make_state(key):
            st = build_staging(self.cfg, self.n_stages,
                               ref.init_params(self.arch, key), act_dtype=act)
            return st.staged, st.shared

        self.init = jax.jit(make_state, out_shardings=(staged_sh, shared_sh))
        self.opt_init = jax.jit(opt_init, out_shardings=opt_sh)
        self.step = jax.jit(
            step, in_shardings=(staged_sh, shared_sh, sh["consts"], opt_sh,
                                self.batch_sh),
            out_shardings=(staged_sh, shared_sh, opt_sh,
                           NamedSharding(self.mesh, P())),
            donate_argnums=(0, 1, 3))
        L = self.arch["n_layers"]

        def flat(staged, shared):
            blocks = jax.tree.map(lambda x: x.reshape(L, *x.shape[2:]),
                                  staged["blocks"])
            return {**shared, "blocks": blocks}

        self.norms = jax.jit(lambda t: ref.leaf_norms(
            flat(t["staged"], t["shared"])))
        # the change from the seed's weights, made anew inside the one
        # program: no second copy of the staged weights is held
        self.delta = jax.jit(lambda a, b, key: ref.leaf_norms(jax.tree.map(
            lambda x, y: x - y, flat(a, b), flat(*make_state(key)))))
        self.state = None

    def load(self, seed: int) -> None:
        self.seed = seed
        with jax.set_mesh(self.mesh):
            staged, shared = self.init(self.ref.seed_key(seed))
            opt_state = self.opt_init({"staged": staged, "shared": shared})
        self.state = (staged, shared, opt_state)
        self.done = 0

    def run(self, n: int, log_every: int = 0) -> list:
        """``n`` more steps; returns when the last step's state is ready.
        With ``log_every`` 1 each step's loss is read back."""
        hist = []
        staged, shared, opt_state = self.state
        self.state = None
        with jax.set_mesh(self.mesh):
            for _ in range(n):
                b = traffic_lib.batch(self.seed, self.done, rows=self.batch,
                                      seq_len=self.seq,
                                      vocab=self.arch["vocab_size"])
                b = jax.device_put(b, self.batch_sh)
                staged, shared, opt_state, metrics = self.step(
                    staged, shared, self.consts, opt_state, b)
                jax.block_until_ready((staged, shared, opt_state))
                self.done += 1
                if log_every:
                    hist.append({"loss": float(metrics["loss"])})
        self.state = (staged, shared, opt_state)
        return hist

    def check_steps(self, k: int) -> Dict[str, np.ndarray]:
        hist = self.run(1, log_every=1)
        with jax.set_mesh(self.mesh):
            grad = np.asarray(self.norms(self.state[2].mu)) / (
                1 - self.opt["beta1"])
        t0 = time.perf_counter()
        hist += self.run(k - 1, log_every=1)
        step_s = (time.perf_counter() - t0) / (k - 1)
        with jax.set_mesh(self.mesh):
            delta = np.asarray(self.delta(self.state[0], self.state[1],
                                          self.ref.seed_key(self.seed)))
        return {"loss": np.asarray([h["loss"] for h in hist]), "grad": grad,
                "delta": delta, "step_s": step_s}

    def free(self) -> None:
        for leaf in jax.tree.leaves(self.state):
            leaf.delete()
        self.state = None
