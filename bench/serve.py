"""Serving cells: the program's continuous-batching engine under a mix.

Set-up builds the system under test from the configuration and the seed:
block-sparse weights made on the device (``weights.py``), the program's own
``compress_params`` (BCSR at the configuration's block), a ``ServeEngine``
with the mix's engine settings, and one warm-up request per compiled tick
width. A closed-loop mix also opens its sessions and prefills them here.

The window then drives ``ServeEngine.submit`` / ``ServeEngine.step`` for
exactly ``seconds``. Token times come from the engine's stream callback on
the host clock; an open-loop request is timed from when it was due.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import loadgen, readings


class _Client:
    """One request's client-side record: due time, submit time, and the
    time of every streamed token."""
    __slots__ = ("job", "due", "submitted", "times", "tokens", "done",
                 "replaced")

    def __init__(self, job, due):
        self.job, self.due = job, due
        self.submitted = None
        self.times: list[float] = []
        self.tokens: list[int] = []
        self.done = False
        self.replaced = False

    def on_token(self, ev):
        self.times.append(time.perf_counter())
        self.tokens.append(int(ev.token))
        self.done = bool(ev.done)


def program_model(cfg: dict, remat: bool = False):
    """The program's ModelConfig and Model for a benchmark configuration."""
    from repro.configs.base import ModelConfig
    from repro.models.transformer import make_model
    m = cfg["model"]
    mcfg = ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        head_dim=m.get("head_dim"), d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], qk_norm=bool(cfg.get("qk_norm")),
        rope_theta=float(m["rope_theta"]),
        tie_embeddings=bool(m["tie_word_embeddings"]), norm="rmsnorm",
        mlp_gated=True, act=m["hidden_act"],
        compute_dtype=cfg["compute_dtype"], param_dtype="float32")
    return mcfg, make_model(mcfg, remat=remat)


def compressed_params(cfg: dict, seed: int, model):
    """Seeded block-sparse weights through the program's compression."""
    import jax
    from repro.sparse.compress import CompressionPlan, compress_params
    from bench import weights as W

    dense = W.make_weights_fn(cfg)(W.seed_key(seed))
    want = jax.tree.map(lambda a: (a.shape, a.dtype),
                        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), dense)
    if want != got:
        raise RuntimeError("the program's parameter layout differs from the "
                           f"benchmark's weights: {want} != {got}")
    plan = CompressionPlan(block=tuple(cfg["compression"]["block"]))
    cp = compress_params(dense, plan)
    del dense
    layer = cp.sparse.get("layers", {}).get("b0_attn", {})
    have = {(g, n) for g in ("attn", "mlp") for n in layer.get(g, {})}
    need = {(g, n) for g, n, _, _ in W.projections(cfg).values()}
    if have != need:
        raise RuntimeError(f"the program left {sorted(need - have)} dense; "
                           "the configuration compresses every projection")
    return cp


class ServeRun:
    """Set-up, window and readings of one serving run."""

    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float):
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.seconds = float(seconds)
        self.eng_cfg = loadgen.engine_sizes(mix)
        self.vocab = cfg["model"]["vocab_size"]
        self.plans: list[tuple] = []       # (width, start_pos, n_tokens)
        self.clients: list[_Client] = []

    # -- set-up ---------------------------------------------------------

    def setup(self):
        from repro.serve.engine import EngineConfig, ServeEngine
        _, model = program_model(self.cfg)
        self.params = compressed_params(self.cfg, self.seed, model)
        self.blocks = readings.stored_blocks(self.params)
        e = self.eng_cfg
        self.engine = ServeEngine(model, self.params, EngineConfig(
            max_batch=e["max_batch"], prefill_chunk=e["prefill_chunk"],
            page_size=e["page_size"], max_seq_len=e["max_seq_len"],
            token_budget=e.get("token_budget")))
        self._warm_up()
        sched = self.engine.scheduler
        orig = sched.next_tick

        def recording_next_tick(now=0.0):
            plan = orig(now=now)
            if plan is not None:
                self.plans.append((plan.width, plan.start_pos.copy(),
                                   plan.n_tokens.copy()))
            return plan

        sched.next_tick = recording_next_tick
        if self.mix["kind"] == "closed_sessions":
            self._open_sessions()
        else:
            self._warm_in()

    def _warm_up(self):
        """Compile every tick width the window uses: one request whose
        prompt spans a full prefill chunk and a partial one, then decodes."""
        from repro.serve.api import Request
        chunk = self.eng_cfg["prefill_chunk"]
        rng = np.random.default_rng([self.seed, 7])
        prompt = rng.integers(0, self.vocab, chunk + chunk // 2)
        self.engine.submit(Request(prompt=prompt, max_new_tokens=3))
        while self.engine.scheduler.has_work():
            self.engine.step()

    def _submit(self, client: _Client):
        from repro.serve.api import Request
        client.submitted = time.perf_counter()
        self.engine.submit(Request(prompt=client.job.prompt,
                                   max_new_tokens=client.job.max_new_tokens),
                           stream=client.on_token)

    def _open_sessions(self):
        self.pool = loadgen.closed_sessions(self.mix, self.seed, self.vocab)
        self.next_session = 0
        for _ in range(int(self.mix["sessions"])):
            self._start_session(None)
        # set-up the traffic needs: every session's context prefilled
        while not all(c.tokens for c in self.clients):
            self.engine.step()

    def _start_session(self, due):
        job = self.pool[self.next_session % len(self.pool)]
        self.next_session += 1
        c = _Client(job, due)
        self.clients.append(c)
        self._submit(c)

    # -- window ---------------------------------------------------------

    def _drive(self, until: float):
        """Open loop: submit every job when it is due, step the engine
        while it has work, otherwise wait for the next due time."""
        from jax.profiler import TraceAnnotation
        eng, jobs = self.engine, self.jobs
        while True:
            now = time.perf_counter()
            if now >= until:
                return
            while (self.next_job < len(jobs)
                   and self.t_base + jobs[self.next_job].due <= now):
                job = jobs[self.next_job]
                c = _Client(job, self.t_base + job.due)
                self.clients.append(c)
                with TraceAnnotation("bench.submit"):
                    self._submit(c)
                self.next_job += 1
            if eng.scheduler.has_work():
                with TraceAnnotation("bench.step"):
                    eng.step()
            else:
                nxt = (self.t_base + jobs[self.next_job].due
                       if self.next_job < len(jobs) else until)
                with TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(nxt, until) - now))

    def _warm_in(self):
        """Open loop: arrivals start ``warm_s`` before the window, so the
        window opens on a queue in steady state, not an empty engine."""
        self.jobs = loadgen.open_poisson(self.mix, self.seed, self.seconds,
                                         self.vocab)
        self.next_job = 0
        self.t_base = time.perf_counter() + float(self.mix.get("warm_s", 0))
        self._drive(self.t_base)

    def window(self, on_open=None):
        """Drive the engine for ``seconds``; returns (t_open, t_close).
        Host work is annotated (``bench.step``, ``bench.submit``,
        ``bench.wait``) so a trace can name the device's idle gaps."""
        from jax.profiler import TraceAnnotation
        self.reg_open = self.engine.metrics.snapshot()
        self.plans.clear()
        if on_open is not None:
            on_open()
        eng = self.engine
        if self.mix["kind"] == "open_poisson":
            t0 = self.t_base                 # due times count from here
            end = t0 + self.seconds
            self._drive(end)
        else:
            t0 = time.perf_counter()
            end = t0 + self.seconds
            while time.perf_counter() < end:
                with TraceAnnotation("bench.step"):
                    eng.step()
                for c in list(self.clients):
                    if c.done and not c.replaced:
                        c.replaced = True
                        with TraceAnnotation("bench.submit"):
                            self._start_session(time.perf_counter())
        t1 = time.perf_counter()
        self.reg_close = eng.metrics.snapshot()
        self.t_open, self.t_close_target, self.t_close = t0, end, t1
        return t0, t1

    def window_requests(self) -> list:
        """Open loop: the requests due in the window."""
        return [c for c in self.clients
                if c.due is not None and self.t_open <= c.due < self.t_close_target]

    # -- readings -------------------------------------------------------

    def stored_weights(self) -> int:
        """Weights in the stored blocks, summed over every layer."""
        br, bc = self.cfg["compression"]["block"]
        return int(sum(int(b.sum()) for b in self.blocks.values())) * br * bc

    def end_to_end(self) -> dict:
        """The window's host-clock metrics (every request, every gap). The
        window runs from its open to the end of the tick that was running
        at the close, so the rate counts whole ticks over their time."""
        t0, t1 = self.t_open, self.t_close
        n_tok, gaps = 0, []
        for c in self.clients:
            prev = None
            for t in c.times:
                if t < t0:
                    prev = t
                    continue
                if t > t1:
                    break
                n_tok += 1
                if prev is not None:
                    gaps.append(t - prev)
                prev = t
        out = {"output_tok_s": n_tok / (t1 - t0),
               "token_gap_p95_ms": _pctl(gaps, 95) * 1e3,
               "n_gaps": len(gaps), "n_tokens": n_tok, "window_s": t1 - t0}
        if self.mix["kind"] == "open_poisson":
            ttft = []
            for c in self.window_requests():
                first = c.times[0] if c.times and c.times[0] <= t1 else None
                ttft.append((first if first is not None else t1) - c.due)
            out["ttft_p90_ms"] = _pctl(ttft, 90) * 1e3
            out["n_requests"] = len(ttft)
            late = [c.submitted - c.due for c in self.window_requests()]
            out["submit_late_ms_p50"] = _pctl(late, 50) * 1e3
            out["submit_late_ms_max"] = max(late) * 1e3
        return out

    def served(self) -> list[tuple[np.ndarray, list[int]]]:
        """(prompt, served tokens) of every request that streamed any."""
        return [(c.job.prompt, list(c.tokens)) for c in self.clients
                if c.tokens]

    def counts(self) -> tuple[int, int]:
        """(requests attempted in the window, requests failed): requests
        due in the window, or sessions served in it."""
        if self.mix["kind"] == "open_poisson":
            return len(self.window_requests()), 0
        return len(self.clients), 0

    def check(self) -> dict:
        """Served tokens against the reference, once the program's state
        is freed. Returns {name: {"value", "limit"}}: the widest and the
        mean logit gap, and how many of the sample's target
        tokens were not served."""
        import gc
        from bench import check
        served = self.served()
        self.engine = self.params = None
        gc.collect()
        target = int(self.mix["check"]["served_tokens"])
        picked = check.sample_requests(
            served, self.seed,
            None if self.mix["check"].get("all_served") else target)
        got = check.logit_gaps(
            self.cfg, self.seed, served, picked, self.eng_cfg["max_seq_len"],
            loadgen.max_len(self.mix["output_tokens"]))
        limits = self.cfg["limits"]
        print(f"bench: logit gaps widest {got['logit_gap']!r} mean "
              f"{got['mean_logit_gap']!r} over {got['n_tokens']} tokens",
              file=sys.stderr)
        out = {k: {"value": got[k], "limit": limits[k]}
               for k in ("logit_gap", "mean_logit_gap")}
        out["tokens_short"] = {"value": max(0, target - got["n_tokens"]),
                               "limit": 0}
        return out


def _pctl(xs, q) -> float:
    if not xs:
        return float("nan")
    return float(np.percentile(np.asarray(xs, np.float64), q))
