"""Serving cells: the engine built as ``launch/serve.py`` builds it, driven
through ``ServeEngine.submit`` / ``ServeEngine.step`` by an open or a
closed loop, and checked against the plain reference afterwards.

The drivers ``serve_open`` and ``serve_closed`` differ only in how requests
arrive; everything else is here.
"""

from __future__ import annotations

import gc
import sys
import time
from typing import Dict, List

import numpy as np

from chipbench import flops, traffic as T, weights
from chipbench.runtime import Check, RequestRecord, Window
from chipbench.trace import Capture


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file written in
    the source's (Hugging Face) key names."""
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=cfg.get("model_type", "dense"), family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=bool(cfg["use_qkv_bias"]), rope_theta=float(cfg["rope_theta"]),
        rope_fraction=float(cfg["rope_pct"]), norm_eps=float(cfg["norm_eps"]),
        dtype=cfg["torch_dtype"])


def _check_params(params, mcfg) -> None:
    """The seeded weights must be exactly what the program's schema asks."""
    from repro.models import schema

    want = {k: (tuple(s.shape), str(s.dtype))
            for k, s in schema.param_structs(mcfg).items()}
    got = {k: (tuple(v.shape), str(v.dtype)) for k, v in params.items()}
    if want != got:
        raise ValueError(f"seeded weights do not match the program's schema: "
                         f"{sorted(set(want.items()) ^ set(got.items()))}")


class Server:
    """One engine at the cell's sizes, with the benchmark's spans around
    its device calls."""

    def __init__(self, run):
        import jax
        from repro.core.compat import make_mesh
        from repro.models.config import ParallelCtx
        from repro.serve.engine import ServeEngine

        self.run = run
        cfg, tr = run.cell.config_data, run.cell.traffic_data
        self.cfg, self.tr = cfg, tr
        self.mcfg = model_config(cfg)
        self.seed32 = T.derive_seed32("weights", run.seed)
        mesh = make_mesh((1, 1), ("data", "model"), axis_types="auto",
                         devices=run.devices[:1])
        pctx = ParallelCtx.from_mesh(mesh, remat=False, inference=True)
        params = weights.make_params(cfg, self.seed32, device=run.devices[0])
        _check_params(params, self.mcfg)
        jax.block_until_ready(params)
        self.eng = ServeEngine(
            self.mcfg, mesh, pctx, params, slots=int(tr["slots"]),
            max_len=int(tr["max_len"]),
            prefill_chunk=int(tr["prefill_chunk"]),
            page_tokens=int(tr.get("page_tokens", 64)),
            temperature=0.0, seed=0)
        self.spans = run.spans
        self._wrap("decode_step", "bench.decode_call", block=True)
        self._wrap("chunk_step", "bench.prefill_call", block=False)

    def _wrap(self, attr: str, name: str, *, block: bool) -> None:
        """Span each call of one of the engine's compiled programs.  The
        decode span runs through the logits being ready, which the engine
        fetches right after; the prefill span covers the dispatch only, so
        the engine's overlap of prefill with its cache writes is kept."""
        import jax

        fn = getattr(self.eng, attr)
        spans = self.spans

        def call(*a):
            with spans.span(name):
                out = fn(*a)
                if block:
                    jax.block_until_ready(out[0])
            return out

        setattr(self.eng, attr, call)

    def warm_up(self) -> None:
        """Every shape the window uses: the chunk program with a full and a
        partial chunk, the decode program, and the engine's eager per-slot
        slice and write for every slot index."""
        eng = self.eng
        chunk = eng.chunk
        rng = np.random.default_rng(0)
        for _ in range(2):
            reqs = [eng.submit(rng.integers(0, self.mcfg.vocab_size,
                                            size=chunk + 5, dtype=np.int32),
                               max_new=3)
                    for _ in range(eng.B)]
            eng.run()
            if not all(r.done for r in reqs):
                raise RuntimeError("warm-up requests did not finish")

    def free(self) -> None:
        self.eng = None
        gc.collect()


class Loop:
    """The window: submit, step, and timestamp every served token."""

    def __init__(self, server: Server, run):
        self.s, self.run = server, run
        self.eng = server.eng
        self.records: List[RequestRecord] = []
        self.live: Dict[int, tuple] = {}          # id(GenRequest) -> (req, rec)
        self.finished: List[tuple] = []
        self.counters = {"prefill_tokens": 0, "decode_tokens": 0,
                         "tokens_out": 0, "model_flops": 0.0,
                         "engine_steps": 0, "device_calls": 0}

    def submit(self, req: T.Req, due: float) -> None:
        prompt = T.prompt_tokens(self.run.seed, req, self.s.mcfg.vocab_size)
        with self.run.spans.span("bench.submit"):
            g = self.eng.submit(prompt, max_new=req.max_new)
        rec = RequestRecord(req.index, due, submit=time.perf_counter())
        self.records.append(rec)
        self.live[id(g)] = (g, rec)

    def step(self, t1: float) -> None:
        before = {k: (g.fed, len(g.out)) for k, (g, _) in self.live.items()}
        steps0, calls0 = self.eng.steps, self.eng.device_calls
        with self.run.spans.span("bench.step"):
            self.eng.step()
        now = time.perf_counter()
        in_window = now <= t1
        cfg = self.s.cfg
        for k, (g, rec) in list(self.live.items()):
            fed0, out0 = before[k]
            if rec.admit is None and g.admit_t is not None:
                rec.admit = g.admit_t
            new = len(g.out) - out0
            for j in range(out0, len(g.out)):
                rec.token_times.append(
                    g.first_token_t if j == 0 else now)
            if in_window:
                plen = len(g.prompt)
                self.counters["prefill_tokens"] += g.fed - fed0
                self.counters["model_flops"] += flops.prefill_flops(
                    cfg, fed0, g.fed)
                for j in range(out0, len(g.out)):
                    if j == 0:      # chosen by the last prefill chunk
                        self.counters["model_flops"] += (
                            2.0 * cfg["hidden_size"] * cfg["vocab_size"])
                    else:
                        self.counters["decode_tokens"] += 1
                        self.counters["model_flops"] += flops.token_flops(
                            cfg, plen + j, logits=True)
                self.counters["tokens_out"] += new
            if g.done:
                self.finished.append((g, rec))
                del self.live[k]
        if in_window:
            self.counters["engine_steps"] += self.eng.steps - steps0
            self.counters["device_calls"] += self.eng.device_calls - calls0

    @property
    def busy(self) -> bool:
        return bool(self.eng.active or self.eng.queue or self.eng.preempted)


def open_window(loop: Loop, reqs, t0: float, t1: float, capture) -> None:
    """Submit each request when it is due, whatever the engine's state."""
    i = 0
    while True:
        now = time.perf_counter()
        capture.poll(t0, now)
        if capture.done:
            return
        while i < len(reqs) and t0 + reqs[i].arrival_s <= now:
            loop.submit(reqs[i], t0 + reqs[i].arrival_s)
            i += 1
        if now >= t1:
            return
        if loop.busy:
            loop.step(t1)
        else:
            nxt = t0 + reqs[i].arrival_s if i < len(reqs) else t1
            with loop.run.spans.span("bench.wait_arrival"):
                time.sleep(max(0.0, min(nxt, t1) - now))


def closed_window(loop: Loop, pool, outstanding: int, t0: float, t1: float,
                  capture) -> None:
    """Keep ``outstanding`` requests in the engine until the window ends."""
    while True:
        now = time.perf_counter()
        capture.poll(t0, now)
        if now >= t1 or capture.done:
            return
        while len(loop.live) < outstanding:
            loop.submit(next(pool), now)
        loop.step(t1)


def drain(loop: Loop, t1: float, seconds: float, first_only: bool) -> None:
    """Step on after the window, at most ``seconds``: until every request
    has its first token (``first_only``), or until the engine is empty."""
    end = time.perf_counter() + seconds
    while loop.busy and time.perf_counter() < end:
        if first_only and all(r.token_times for _, r in loop.live.values()):
            return
        loop.step(t1)


def run_window(run, arrivals: str) -> Window:
    """Set up, warm up, measure ``run.seconds``, drain, check."""
    tr = run.cell.traffic_data
    server = Server(run)
    server.warm_up()
    loop = Loop(server, run)
    capture = Capture(run.trace, float(tr.get("trace_after_s", 2.0)),
                      float(tr.get("trace_s", 3.0)))
    compiles0 = run.clock.compiles
    t0 = time.perf_counter()
    setup_s = t0 - run.t_start
    t1 = t0 + run.seconds
    if arrivals == "open":
        open_window(loop, T.open_loop(tr, run.seconds, run.seed), t0, t1,
                    capture)
    else:
        closed_window(loop, T.closed_pool(tr),
                      int(tr["outstanding"]), t0, t1, capture)
    capture.stop()
    t1 = capture.end(t1)
    compiles = run.clock.compiles - compiles0
    if arrivals == "open":
        # bounded drain: every request due in the window gets its first
        # token; a closed loop has nothing due, and stops here
        drain(loop, t1, float(tr.get("drain_s", 60.0)), first_only=True)
    w = Window(setup_s=setup_s, t0=t0, t1=t1, compiles_in_window=compiles,
               overhead_s=capture.overhead_s,
               counters=dict(loop.counters), requests=loop.records,
               spans=list(run.spans.items))
    w.attempted = len(loop.records)
    w.failed = sum(1 for r in loop.records if not r.token_times) \
        if arrivals == "open" else 0
    w.memory_peak_bytes = run.memory_peak()
    w.trace = capture.summary()
    finished = loop.finished
    server.free()
    del loop
    gc.collect()
    w.checks = check(run, server, finished)
    return w


def sample(run, finished, n: int):
    """A seeded sample of ``n`` finished requests with the longest in it:
    (prompts, served tokens)."""
    longest = max(range(len(finished)),
                  key=lambda i: (len(finished[i][0].out),
                                 len(finished[i][0].prompt)))
    rest = [i for i in range(len(finished)) if i != longest]
    T.derive_rng("sample", run.seed).shuffle(rest)
    pick = [longest] + rest[:n - 1]
    return ([finished[i][0].prompt for i in pick],
            [list(finished[i][0].out) for i in pick])


def gaps(cfg: dict, seed32: int, prompts, served, width: int, device,
         control: str = None):
    """Widest gap below the reference's best logit of the served tokens,
    and, with ``control``, of the tokens that reference mode would put
    first at the same positions."""
    from chipbench.reference.stablelm import Reference, served_batch, \
        widest_gap

    tokens, where, chosen = served_batch(prompts, served, width)
    ref = np.asarray(Reference(cfg, "highest", device=device).logits(
        seed32, tokens, where))
    out = {"served": widest_gap(ref, chosen), "tokens": len(chosen)}
    if control is not None:
        low = np.asarray(Reference(cfg, control, device=device).logits(
            seed32, tokens, where))
        out["control"] = widest_gap(ref, low.argmax(axis=-1))
    return out


def check(run, server: Server, finished) -> Dict[str, Check]:
    """Compare a seeded sample of finished requests with the reference:
    the widest gap by which a served token's reference logit lies below the
    reference's best, and requests served short."""
    tr = run.cell.traffic_data
    if not finished:
        return {"finished_requests": Check(0.0, -1.0)}
    short = sum(1 for g, _ in finished if len(g.out) != g.max_new)
    prompts, served = sample(run, finished, int(tr["check_requests"]))
    g = gaps(server.cfg, server.seed32, prompts, served, int(tr["max_len"]),
             run.devices[0])
    print(f"[check] {len(prompts)} requests, {g['tokens']} served tokens "
          f"compared", file=sys.stderr)
    return {"max_logit_gap": Check(g["served"],
                                   float(tr["limits"]["max_logit_gap"])),
            "served_short": Check(float(short), 0.0)}


def itl_ms(w: Window) -> List[float]:
    """Gaps between consecutive tokens of one request, where the later
    token came inside the window."""
    out = []
    for r in w.requests:
        ts = r.token_times
        out += [1e3 * (b - a) for a, b in zip(ts, ts[1:]) if b <= w.t1]
    return out
