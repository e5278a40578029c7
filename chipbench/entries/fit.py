"""Entry ``fit``: the window drives ``repro.api.fit``, i.e. its ``Trainer``
loop over ``make_train_step``'s jitted step with donated params and AdamW
state, as ``api.fit`` builds it.  The weights are the benchmark's, made on
the device from the seed; the ``Trainer`` makes each step's batch itself.

One object lives from set-up to the end: the first steps, read for the
comparison, and the window go through the same ``api.fit`` call on the same
compiled step and state.
"""
from __future__ import annotations

import time
from pathlib import Path
from typing import Dict, List

import importlib

import jax
import numpy as np


# Checkpoints stay off: the first is due after the last step.  A SIGTERM
# makes the Trainer save its state; under a regular file that directory
# cannot be made, so the save fails instead of writing gigabytes.
NEVER = 10 ** 9
CKPT_DIR = Path(__file__).resolve().parents[2] / "BENCHMARK.json" / "ckpt"


def _quiet(*_a, **_k):
    pass


class Entry:
    def __init__(self, config: dict, traffic: dict, devices):
        from repro import api
        from repro.configs.base import ArchConfig
        from repro.data.pipeline import DataConfig
        from repro.train.optimizer import OptimizerConfig
        from repro.train.step import make_train_step
        from repro.train.trainer import TrainerConfig

        self._api, self._data_cls, self._trainer_cls = (api, DataConfig,
                                                        TrainerConfig)
        ref = importlib.import_module(
            f"chipbench.references.{config['reference']}")
        self.ref = ref
        self.arch, self.opt = config["arch"], config["optimizer"]
        self.cfg = ArchConfig(**self.arch)
        self.batch, self.seq = traffic["batch"], traffic["seq_len"]
        self.tokens_per_step = self.batch * self.seq
        opt = OptimizerConfig(**{k: v for k, v in self.opt.items()
                                 if k != "no_decay"})
        step_fn, _model, opt_init = make_train_step(
            self.cfg, opt, n_microbatches=traffic["microbatches"])
        # as api.fit jits it: donated state, outputs reuse the inputs
        self.step = jax.jit(step_fn, donate_argnums=(0, 1))
        self.init = jax.jit(lambda k: ref.init_params(self.arch, k))
        self.opt_init = jax.jit(opt_init)
        self.norms = jax.jit(ref.leaf_norms)
        # the change from the seed's weights, made anew inside the one
        # program, leaf by leaf: no second copy of the weights is held, so
        # the process's memory peak stays the step's
        self.delta = jax.jit(lambda p, key: ref.leaf_norms(jax.tree.map(
            lambda x, y: x - y, p, ref.init_params(self.arch, key))))
        self.state = None

    def load(self, seed: int) -> None:
        self.seed = seed
        params = self.init(self.ref.seed_key(seed))
        self.state = {"params": params, "opt_state": self.opt_init(params)}
        self.data = self._data_cls(vocab_size=self.cfg.vocab_size,
                                   seq_len=self.seq, global_batch=self.batch,
                                   seed=seed, kind="zipf")
        self.done = 0

    def run(self, n: int, log_every: int = NEVER) -> List[Dict]:
        """``n`` more steps through ``api.fit``; returns when the last step's
        state is ready (the Trainer blocks on it)."""
        harp = self._api.HarpConfig(
            seq_len=self.seq, global_batch=self.batch,
            trainer=self._trainer_cls(
                total_steps=self.done + n, ckpt_dir=str(CKPT_DIR),
                ckpt_every=NEVER, log_every=log_every))
        res = self._api.fit(self.cfg, harp, train_step=self.step,
                            state=self.state, data_cfg=self.data,
                            start_step=self.done, log_fn=_quiet)
        self.state, self.done = res["state"], res["final_step"]
        return res["history"]

    def check_steps(self, k: int) -> Dict[str, np.ndarray]:
        """The first ``k`` steps, read for the comparison; the seconds of a
        step after the first come back under ``step_s``."""
        hist = self.run(1, log_every=1)
        grad = np.asarray(self.norms(self.state["opt_state"].mu)) / (
            1 - self.opt["beta1"])
        t0 = time.perf_counter()
        hist += self.run(k - 1, log_every=1)
        step_s = (time.perf_counter() - t0) / (k - 1)
        delta = np.asarray(self.delta(self.state["params"],
                                      self.ref.seed_key(self.seed)))
        return {"loss": np.asarray([h["loss"] for h in hist]), "grad": grad,
                "delta": delta, "step_s": step_s}

    def free(self) -> None:
        for leaf in jax.tree.leaves(self.state):
            leaf.delete()
        self.state = None
