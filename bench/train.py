"""Training cell: SpC-Retrain's mask-frozen debias step on compressed params.

Set-up builds the step as the program's SpC-Retrain pipeline builds its
debias phase (``train/loop.run_spc_retrain_pipeline``): the seeded
block-sparse weights through ``compress_params``, ``split_trainable``, a
frozen ``zero_mask``, the debias optimizer (``prox_adam`` with no
regularizer) and ``make_train_step(..., param_transform=rebuild)`` under
``jax.jit`` with the state donated. It then drives that same state through
the first three steps with the window's own call and feed, recording what
the check compares: each step's loss, the first gradient as the optimizer
got it (from Adam's first moment after step 1) and the parameters' change
after step 3.

The window continues the same object: batches made on the host per step
(``loadgen.train_batch``), one step in flight ahead of the host, until the
first step that ends after ``seconds``.
"""
from __future__ import annotations

import math
import time

from bench import loadgen, readings
from bench.serve import compressed_params, program_model

CHECK_STEPS = 3


def _leaf_names(tree) -> list[str]:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [jax.tree_util.keystr(p) for p, _ in flat]


def _norms(tree) -> list[float]:
    import jax
    import jax.numpy as jnp
    return [float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
            for x in jax.tree.leaves(tree)]


class TrainRun:
    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float):
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.seconds = float(seconds)
        self.vocab = cfg["model"]["vocab_size"]
        self.step_no = 0
        self.done_times: list[float] = []
        self.failed = 0                     # steps with a non-finite loss

    def _batch(self):
        b = loadgen.train_batch(self.mix, self.seed, self.step_no,
                                self.vocab)
        self.step_no += 1
        return b

    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.core.masks import zero_mask
        from repro.core.optimizers import prox_adam
        from repro.sparse.compress import split_trainable
        from repro.train.state import TrainState
        from repro.train.step import make_train_step

        _, model = program_model(self.cfg, remat=True)
        cp = compressed_params(self.cfg, self.seed, model)
        self.blocks = readings.stored_blocks(cp)
        trainable, rebuild = split_trainable(cp)
        del cp
        opt_cfg = self.mix["optimizer"]
        opt = prox_adam(opt_cfg["lr"], lam=0.0)
        self.b1 = 0.9
        step = make_train_step(model, opt, param_transform=rebuild)
        self.step = jax.jit(step, donate_argnums=(0,))
        state = TrainState(params=trainable, opt_state=opt.init(trainable),
                           mask=zero_mask(trainable),
                           step=jnp.zeros((), jnp.int32))
        self.leaves = list(zip(_leaf_names(trainable),
                               [x.shape for x in jax.tree.leaves(trainable)]))
        p0 = jax.tree.map(jnp.copy, trainable)
        self.losses = []
        for k in range(CHECK_STEPS):
            state, metrics = self.step(state, self._batch())
            self.losses.append(float(metrics["loss"]))
            if k == 0:
                # Adam's first moment after one step is (1 - b1) * g
                self.grad1 = [n / (1.0 - self.b1)
                              for n in _norms(state.opt_state.m)]
        self.change = _norms(jax.tree.map(lambda a, b: a - b,
                                          state.params, p0))
        del p0
        self.state = state

    def window(self, on_open=None):
        from jax.profiler import TraceAnnotation
        if on_open is not None:
            on_open()
        t0 = time.perf_counter()
        end = t0 + self.seconds
        inflight = None
        while True:
            with TraceAnnotation("bench.batch"):
                batch = self._batch()        # host work overlaps the step
            if inflight is not None:
                with TraceAnnotation("bench.wait_step"):
                    loss = float(inflight)
                now = time.perf_counter()
                self.done_times.append(now)
                self.failed += not math.isfinite(loss)
                if now >= end:
                    break
            with TraceAnnotation("bench.dispatch"):
                self.state, metrics = self.step(self.state, batch)
            inflight = metrics["loss"]
        self.t_open = t0
        self.t_close = time.perf_counter()
        return t0, self.t_close

    def end_to_end(self) -> dict:
        """Tokens of every step completed in the window over the time from
        its open to the last step's end: the window closes on the first
        step to end after ``seconds``, so it holds whole steps only."""
        tokens = len(self.done_times) * int(self.mix["batch"]) \
            * int(self.mix["seq_len"])
        span = self.done_times[-1] - self.t_open
        return {"train_tok_s": tokens / span,
                "steps": len(self.done_times), "window_s": span}

    def counts(self) -> tuple[int, int]:
        """(steps completed in the window, steps with a non-finite loss)."""
        return len(self.done_times), self.failed

    def stored_weights(self) -> int:
        br, bc = self.cfg["compression"]["block"]
        return int(sum(int(b.sum()) for b in self.blocks.values())) * br * bc

    def check_batches(self) -> list:
        """The batches of the first steps, made again from the seed."""
        return [loadgen.train_batch(self.mix, self.seed, k, self.vocab)
                for k in range(CHECK_STEPS)]

    def check(self) -> dict:
        """The first three steps against the reference (float32, Adam)."""
        import gc
        import sys
        import jax
        from bench import check
        self.state = self.step = None
        gc.collect()
        stats = jax.devices()[0].memory_stats() or {}
        print(f"bench: device bytes in use before the reference "
              f"{stats.get('bytes_in_use')}", file=sys.stderr)
        ref = check.reference_steps(self.cfg, self.mix, self.seed,
                                    self.check_batches())
        got = check.train_gaps(self.losses, check.program_leaves(
            self.leaves, self.grad1, self.change, ref["shapes"]), ref)
        limits = self.cfg["limits"]
        return {k: {"value": got[k], "limit": limits.get(k, 0.0)}
                for k in ("loss_gap", "grad_norm_gap", "change_norm_gap")}
