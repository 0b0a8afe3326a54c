"""Multi-model server: the paper's scheduler over models executing on a chip.

Serve mode (the default) runs a model mix on the local accelerator:

  1. build each model from its config and initialise random parameters from
     ``--seed`` on the device, under ``jax.jit``;
  2. compile prefill per (batch bucket, prompt length) and one decode step
     per batch bucket, ahead of time, and run each once (warm-up);
  3. measure L(b) per model with ``block_until_ready`` around every timed
     call: prefill of the longest prompt plus the decode steps of the
     longest generation;
  4. schedule the mix with Elastic Partitioning (Alg. 1) on a one-device
     ``ClusterSpec`` of the local chip; a partition is read as a time
     share of the chip;
  5. replay a seeded Poisson trace: each request has a prompt and a number
     of tokens to generate, and is answered by greedy prefill plus decode.

Plan mode (``--results``) takes a dry-run results file (launch/dryrun.py),
derives each architecture's roofline L(b, p) table, and prints the
Elastic Partitioning placement of a model mix onto pod partitions.

Usage:
  python -m repro.launch.serve --models chatglm3-6b,mamba2-780m --requests 16
  python -m repro.launch.serve --results results/dryrun.jsonl \
      --rates yi-9b=400,chatglm3-6b=800,mamba2-780m=2000 --pods 4
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import os
import time
from collections.abc import Callable, Sequence
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.elastic import ElasticPartitioning
from repro.core.hardware import AcceleratorSpec, ClusterSpec
from repro.core.latency import LatencyProvider
from repro.core.profiles import ModelProfile
from repro.core.scheduler_base import ScheduleResult
from repro.core.tpulets import load_catalog
from repro.launch import sharding as shr
from repro.launch.mesh import make_serving_mesh
from repro.models.config import ModelConfig, round_up
from repro.models.model import PAD, Model
from repro.models.shard_ctx import mesh_context
from repro.obs.served import RECORDER, Window
from repro.obs.spans import BatchSpan, HostSpan, ServedBatch, SleepSpan
from repro.simulator.events import PoissonArrivals, merge_sorted

#: One 16x16 v5e pod treated as a single partitionable "device".
V5E_POD = AcceleratorSpec(name="v5e-pod-16x16", peak_tflops=197.0 * 256,
                          hbm_gbs=819.0 * 256, hbm_gb=16.0 * 256,
                          ici_gbs=50.0)

#: Share of the largest schedulable scale the replayed trace offers.
LOAD = 0.3

#: Logit agreement bounds, as a fraction of the reference's largest |logit|.
#: Two bf16 programs computing the same function round at different fusion
#: boundaries.  Through a KV cache that rounding stays a fixed error (0.016
#: for chatglm3-6b on a v5e chip); a recurrent state carries each decode
#: step's rounding into the next, so it grows with the steps (0.35 after 16
#: greedy steps of mamba2-780m on the chip, 0.16-0.24 on CPU over five seeds).
#: Broken decode state is off by more: a zeroed SSM conv state 1.25, shifted
#: KV heads 0.63, a RoPE position off by one 0.19.  The float32 CPU tests
#: hold the same paths to 1e-3.
LOGIT_TOL = 0.1
RECURRENT_LOGIT_TOL = 0.5
RECURRENT_KINDS = frozenset({"ssm", "rglru"})


def logit_tol(cfg: ModelConfig) -> float:
    """The agreement bound of a model: looser where decode carries a
    recurrent state."""
    if RECURRENT_KINDS & set(cfg.layer_types()):
        return RECURRENT_LOGIT_TOL
    return LOGIT_TOL


@dataclasses.dataclass(frozen=True)
class ServeShapes:
    """Request shapes a server compiles for.

    A batch of prompts of different lengths runs at its longest, one of
    ``prompt_lens``, the others right-padded (the decode cache carries one
    length per row); requests that stop earlier than the batch's longest
    generation keep only their own tokens.
    """

    prompt_lens: tuple[int, ...] = (128, 256, 384, 512)
    gen_range: tuple[int, int] = (16, 32)      # generated tokens, inclusive
    batch_buckets: tuple[int, ...] = (1, 2, 4)

    @property
    def max_len(self) -> int:
        """Decode cache length: a multiple of the decode kernel's block."""
        return round_up(max(self.prompt_lens) + self.gen_range[1], 256)


#: Shapes for the CPU rehearsal and tests (smoke configs).
SMOKE_SHAPES = ServeShapes(prompt_lens=(16, 32), gen_range=(4, 8),
                           batch_buckets=(1, 2, 4))


def use_compile_cache(root: str) -> str:
    """Keep JAX's persistent compile cache in ``<root>/.jax_cache``.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is changed.  Call this from entry points only, never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class Generation(NamedTuple):
    tokens: np.ndarray          # (B, n_new) greedy tokens
    logits: list                # n_new device arrays (B, V) float32
    finite: bool                # every logit computed was finite
    # host stamps (time.perf_counter): prefill dispatched, first token on
    # the host, last token on the host
    dispatch_s: float = math.nan
    first_token_s: float = math.nan
    done_s: float = math.nan


def _greedy(logits):
    last = logits[:, -1].astype(jnp.float32)
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
    return tok, last, jnp.all(jnp.isfinite(last))


class ModelRunner:
    """One served model: parameters on ``mesh`` and compiled programs.

    Prefill is compiled per (batch, prompt length) and the decode step per
    batch, ahead of time, so a shape that was not compiled fails loudly
    instead of compiling inside a timed window.  ``params`` lets a second
    runner (another ``kernel_impl``) share the first one's weights.
    """

    def __init__(self, cfg: ModelConfig, mesh, shapes: ServeShapes,
                 seed: int, params=None):
        self.cfg, self.mesh, self.shapes = cfg, mesh, shapes
        self.model = Model(cfg)
        self.param_sh = shr.param_shardings(cfg, self.model.param_shapes(),
                                            mesh, fsdp=False)
        self.rep = NamedSharding(mesh, P())
        self.compile_s = 0.0
        self._prefill: dict[tuple[int, int], Callable] = {}
        self._decode: dict[int, Callable] = {}
        self._forward: dict[tuple[int, int, int], Callable] = {}
        self.prefill_ms: dict[int, float] = {}
        self.decode_ms: dict[int, float] = {}
        self.latency_ms: dict[int, float] = {}
        if params is None:
            key = jax.random.key(seed)
            init = self._compile(jax.jit(self.model.init, in_shardings=self.rep,
                                         out_shardings=self.param_sh), key)
            params = jax.block_until_ready(init(key))
        self.params = params
        self.param_bytes = sum(x.size * x.dtype.itemsize
                               for x in jax.tree.leaves(params))

    @property
    def name(self) -> str:
        return self.cfg.name

    def _compile(self, jitted, *args):
        t0 = time.perf_counter()
        with mesh_context(self.mesh, shr.dp_axes(self.mesh)):
            compiled = jitted.lower(*args).compile()
        self.compile_s += time.perf_counter() - t0
        return compiled

    def _tokens(self, b: int, s: int):
        sds = jax.ShapeDtypeStruct((b, s), jnp.int32)
        return sds, shr.batch_shardings(self.cfg, {"t": sds}, self.mesh)["t"]

    def compile(self, batches: Sequence[int] | None = None,
                prompt_lens: Sequence[int] | None = None) -> None:
        """Compile every (batch, prompt length) program, then run each once."""
        model, max_len = self.model, self.shapes.max_len
        batches = batches or self.shapes.batch_buckets
        prompt_lens = prompt_lens or self.shapes.prompt_lens

        def prefill(params, tokens):
            cache = model.init_cache(tokens.shape[0], max_len)
            logits, cache = model.prefill(params, {"tokens": tokens}, cache)
            return (*_greedy(logits), cache)

        def decode(params, cache, tok):
            logits, cache = model.decode_step(params, cache, tok)
            return (*_greedy(logits), cache)

        with RECORDER.setup_span("compile", self.name):
            for b in batches:
                c_sh = shr.cache_shardings(
                    self.cfg, model.cache_shapes(b, max_len), self.mesh)
                step_sds, step_sh = self._tokens(b, 1)
                outs = (step_sh, self.rep, self.rep, c_sh)
                for s in prompt_lens:
                    sds, t_sh = self._tokens(b, s)
                    self._prefill[(b, s)] = self._compile(
                        jax.jit(prefill, in_shardings=(self.param_sh, t_sh),
                                out_shardings=outs), self.params, sds)
                self._decode[b] = self._compile(
                    jax.jit(decode, in_shardings=(self.param_sh, c_sh, step_sh),
                            out_shardings=outs, donate_argnums=1),
                    self.params, model.cache_shapes(b, max_len), step_sds)
        with RECORDER.setup_span("warmup", self.name):
            for b in batches:
                for s in prompt_lens:  # every program runs once
                    self.generate(np.zeros((b, s), np.int32), 2)

    def generate(self, prompts: np.ndarray, n_new: int) -> Generation:
        """Greedy prefill plus ``n_new - 1`` decode steps for one batch.

        ``prompts`` rows may be right-padded with ``PAD`` to the batch's
        prompt length: each row continues its own prompt.  Every step is
        queued before the first token comes to the host, so the device
        never waits on the host inside a batch, however late the host
        wakes; the first token's stamp is thus no earlier than the last
        step's dispatch.  ``serve.prefill/<model>`` spans dispatch to first
        token on the host, ``serve.decode/<model>`` first to last token.
        """
        b, s = prompts.shape
        _, t_sh = self._tokens(b, s)
        with jax.profiler.TraceAnnotation(f"serve.prefill/{self.name}"):
            dispatch_s = time.perf_counter()
            tok, last, ok, cache = self._prefill[(b, s)](
                self.params, jax.device_put(prompts, t_sh))
            tok.copy_to_host_async()
            toks, logits, oks = [tok], [last], [ok]
            decode = self._decode[b]
            for _ in range(n_new - 1):
                tok, last, ok, cache = decode(self.params, cache, tok)
                toks.append(tok)
                logits.append(last)
                oks.append(ok)
            np.asarray(toks[0])
        with jax.profiler.TraceAnnotation(f"serve.decode/{self.name}"):
            first_token_s = time.perf_counter()
            tokens = np.concatenate(jax.device_get(toks), axis=1)
            finite = bool(np.all(jax.device_get(oks)))
            done_s = time.perf_counter()
        return Generation(tokens, logits, finite, dispatch_s, first_token_s,
                          done_s)

    def forward_logits(self, seq: np.ndarray, n_last: int) -> np.ndarray:
        """Teacher-forced float32 logits of the last ``n_last`` positions."""
        b, s = seq.shape
        key = (b, s, n_last)
        if key not in self._forward:
            def forward(params, tokens):
                logits, _ = self.model.forward(params, {"tokens": tokens})
                return logits[:, -n_last:].astype(jnp.float32)

            sds, t_sh = self._tokens(b, s)
            self._forward[key] = self._compile(
                jax.jit(forward, in_shardings=(self.param_sh, t_sh),
                        out_shardings=self.rep), self.params, sds)
        _, t_sh = self._tokens(b, s)
        return np.asarray(self._forward[key](self.params,
                                             jax.device_put(seq, t_sh)))

    def measure(self, reps: int = 3) -> None:
        """L(b) per batch bucket: median prefill of the longest prompt plus
        (longest generation - 1) median decode steps, all on the device."""
        s, g = max(self.shapes.prompt_lens), self.shapes.gen_range[1]
        with RECORDER.setup_span("measure", self.name):
            for b in self.shapes.batch_buckets:
                prompts = jax.device_put(np.zeros((b, s), np.int32),
                                         self._tokens(b, s)[1])
                pre, dec = [], []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    tok, _, _, cache = jax.block_until_ready(
                        self._prefill[(b, s)](self.params, prompts))
                    pre.append(time.perf_counter() - t0)
                    t0 = time.perf_counter()
                    tok, _, _, cache = jax.block_until_ready(
                        self._decode[b](self.params, cache, tok))
                    dec.append(time.perf_counter() - t0)
                self.prefill_ms[b] = float(np.median(pre)) * 1e3
                self.decode_ms[b] = float(np.median(dec)) * 1e3
                self.latency_ms[b] = (self.prefill_ms[b]
                                      + (g - 1) * self.decode_ms[b])


class MeasuredLatency(LatencyProvider):
    """L(b, p) from measured per-bucket latencies; p is a time share.

    Batches between measured buckets interpolate linearly; the scheduler
    is offered no batch above the largest bucket the server compiled.
    L(b) is taken as non-decreasing in b: a bucket measured faster than a
    smaller one is timing noise, and the scheduler, which admits a model's
    largest batch under its SLO, runs smaller batches at low rates.
    """

    def __init__(self, tables: dict[str, dict[int, float]]):
        self.tables = {}  # name -> {b: ms on the whole device}
        for name, table in tables.items():
            worst, self.tables[name] = 0.0, {}
            for b in sorted(table):
                worst = max(worst, table[b])
                self.tables[name][b] = worst
        self.max_batch = min(max(t) for t in tables.values())
        self.batch_sizes = tuple(range(1, self.max_batch + 1))

    def latency_ms(self, prof: ModelProfile, batch: int, p: float) -> float:
        t = self.tables[prof.name]
        bs = sorted(t)
        b_lo = max([b for b in bs if b <= batch], default=bs[0])
        b_hi = min([b for b in bs if b >= batch], default=bs[-1])
        if b_lo == b_hi:
            base = t[b_lo]
        else:
            w = (batch - b_lo) / (b_hi - b_lo)
            base = (1 - w) * t[b_lo] + w * t[b_hi]
        return base / p


def measured_profile(runner: ModelRunner, slo_ms: float) -> ModelProfile:
    """Scheduler profile of a measured model (its L(b) is in the provider)."""
    return ModelProfile(
        name=runner.name, slo_ms=slo_ms, flops_per_req=0.0,
        weight_mb=runner.param_bytes / 1e6, act_mb_per_req=0.0, par1=1.0,
        par_exp=0.0, t0_ms=0.0, l2_util_base=0.0)


def mix_slo_ms(runners: Sequence[ModelRunner]) -> float:
    """SLO of every model in a mix that time-shares one chip.

    The paper sets a model's SLO to twice its solo latency at the largest
    batch (§6.1).  Models that share one chip in time each wait for the
    others' batches, so the solo latency here is one cycle of the mix:
    every model's slowest measured bucket, back to back.  Any one batch
    then fits half the SLO, so low rates are always schedulable.
    """
    return 2.0 * sum(max(r.latency_ms.values()) for r in runners)


def schedule(runners: Sequence[ModelRunner], device
             ) -> tuple[dict[str, float], ScheduleResult]:
    """Elastic Partitioning of the measured mix on the one local device.

    Returns the replay rates (``LOAD`` of the largest schedulable equal
    scale) and the placement at those rates.
    """
    slo = mix_slo_ms(runners)
    profiles = {r.name: measured_profile(r, slo) for r in runners}
    lat = MeasuredLatency({r.name: r.latency_ms for r in runners})
    # the measured provider reads no peaks: the spec only names the device
    acc = AcceleratorSpec(name=device.device_kind, peak_tflops=0.0,
                          hbm_gbs=0.0, hbm_gb=0.0)
    sched = ElasticPartitioning(profiles, cluster=ClusterSpec(acc, n_devices=1),
                                lat=lat)
    lam = sched.max_scale({m: 1.0 for m in profiles}, hi=4096)
    if lam <= 0:
        raise RuntimeError("no request rate of this mix is schedulable")
    rates = {m: lam * LOAD for m in profiles}
    return rates, sched.schedule(rates)


@dataclasses.dataclass(eq=False)
class ServeRequest:
    model: str
    arrival_ms: float
    slo_ms: float
    prompt: np.ndarray
    max_new: int
    output: np.ndarray | None = None
    completion_ms: float | None = None


def make_trace(runners: Sequence[ModelRunner], rates: dict[str, float],
               slo_ms: float, n_requests: int, seed: int
               ) -> list[ServeRequest]:
    """First ``n_requests`` of a seeded Poisson trace over the mix."""
    horizon_ms = 4e3 * n_requests / sum(rates.values())
    gen = PoissonArrivals(seed=seed)
    arrivals = merge_sorted([gen.constant(r.name, rates[r.name], slo_ms, horizon_ms) for r in runners])
    by_name = {r.name: r for r in runners}
    rng = np.random.default_rng(seed)
    shapes = runners[0].shapes
    reqs = []
    for a in arrivals[:n_requests]:
        s = int(rng.choice(shapes.prompt_lens))
        vocab = by_name[a.model].cfg.vocab_size
        reqs.append(ServeRequest(
            model=a.model, arrival_ms=a.arrival_ms, slo_ms=a.slo_ms,
            prompt=rng.integers(0, vocab, s, dtype=np.int32),
            max_new=int(rng.integers(shapes.gen_range[0],
                                     shapes.gen_range[1] + 1))))
    return reqs


def replay(runners: Sequence[ModelRunner], reqs: list[ServeRequest],
           caps: dict[str, int]) -> None:
    """Serve ``reqs`` in real time, one batch at a time, model by model.

    A batch takes a model's queued requests, oldest first, of any prompt
    length, up to the model's scheduled batch rounded down to a compiled
    bucket; its prompts are right-padded with ``PAD`` to the longest.  The
    recorder's window (a fresh one per call) logs each step; a batch enters
    it once it has answered.
    """
    queues: dict[str, list[int]] = {r.name: [] for r in runners}
    log = RECORDER.open_window(reqs)
    t_start = log.t0_s
    idx = 0
    try:
        while idx < len(reqs) or any(queues.values()):
            now_ms = (time.perf_counter() - t_start) * 1e3
            while idx < len(reqs) and reqs[idx].arrival_ms <= now_ms:
                queues[reqs[idx].model].append(idx)
                log.admitted_ms[idx] = now_ms
                idx += 1
            ran = False
            for runner in runners:
                q = queues[runner.name]
                if not q:
                    continue
                with jax.profiler.TraceAnnotation("serve.form"):
                    t_form = time.perf_counter()
                    want = min(len(q), caps[runner.name])
                    b = max(x for x in runner.shapes.batch_buckets if x <= want)
                    ids, queues[runner.name] = q[:b], q[b:]
                    batch = [reqs[i] for i in ids]
                    row_lens = tuple(len(r.prompt) for r in batch)
                    prompts = np.full((b, max(row_lens)), PAD, np.int32)
                    for k, r in enumerate(batch):
                        prompts[k, :len(r.prompt)] = r.prompt
                    n_new = max(r.max_new for r in batch)
                    t_formed = time.perf_counter()
                log.forms.append(HostSpan("serve.form", log.ms(t_form),
                                          log.ms(t_formed)))
                with jax.profiler.TraceAnnotation(f"serve.batch/{runner.name}"):
                    t_batch = time.perf_counter()
                    out = runner.generate(prompts, n_new)
                if not out.finite:
                    raise FloatingPointError(f"{runner.name}: non-finite logits")
                t_done = (time.perf_counter() - t_start) * 1e3
                bid = len(log.batches)
                log.batches.append(ServedBatch(
                    BatchSpan("batch", 0, 0, log.ms(t_batch), t_done,
                              runner.name, b),
                    bid, prompts.shape[1], n_new, tuple(ids),
                    log.ms(out.dispatch_s), log.ms(out.first_token_s),
                    log.ms(out.done_s), row_lens))
                for k, (i, r) in enumerate(zip(ids, batch)):
                    r.output = out.tokens[k, :r.max_new]
                    r.completion_ms = t_done
                    log.batch[i], log.done_ms[i] = bid, t_done
                ran = True
            if not ran and idx < len(reqs):
                due_ms = reqs[idx].arrival_ms
                with jax.profiler.TraceAnnotation("serve.sleep"):
                    t_sleep = time.perf_counter()
                    time.sleep(max(due_ms - now_ms, 0.0) / 1e3)
                    woke_ms = (time.perf_counter() - t_start) * 1e3
                log.sleeps.append(SleepSpan("serve.sleep", log.ms(t_sleep),
                                            due_ms, woke_ms))
    finally:
        log.open = False


@dataclasses.dataclass
class ServeRun:
    runners: list[ModelRunner]
    rates: dict[str, float]
    placement: ScheduleResult
    requests: list[ServeRequest]
    window: Window | None = None        # the recorder's log of the replay

    def summary(self, runner: ModelRunner) -> dict:
        mine = [r for r in self.requests if r.model == runner.name]
        done = [r for r in mine if r.output is not None]
        return {
            "model": runner.name,
            "param_bytes": runner.param_bytes,
            "compile_s": runner.compile_s,
            "L_ms": runner.latency_ms,
            "prefill_ms": runner.prefill_ms,
            "decode_ms": runner.decode_ms,
            "rate_req_s": self.rates[runner.name],
            "requests": len(mine),
            "served": len(done),
            "tokens": int(sum(len(r.output) for r in done)),
            "slo_violations": sum(r.completion_ms - r.arrival_ms > r.slo_ms
                                  for r in done),
        }


def run(cfgs: Sequence[ModelConfig], *, shapes: ServeShapes = ServeShapes(),
        n_requests: int = 16, seed: int = 0, mesh=None) -> ServeRun:
    """Build, compile, measure, schedule and replay one model mix."""
    mesh = mesh if mesh is not None else make_serving_mesh(jax.devices()[:1])
    runners = [ModelRunner(cfg, mesh, shapes, seed + i)
               for i, cfg in enumerate(cfgs)]
    for r in runners:
        r.compile()
        r.measure()
    rates, placement = schedule(runners, mesh.devices.flat[0])
    caps = {r.name: 1 for r in runners}
    for let in placement.gpulets:
        for a in let.assignments:
            caps[a.model] = max(caps[a.model], a.batch)
    reqs = make_trace(runners, rates, mix_slo_ms(runners), n_requests, seed)
    replay(runners, reqs, caps)
    return ServeRun(runners, rates, placement, reqs, RECORDER.window)


def agreement(tokens: np.ndarray, logits: np.ndarray,
              ref_logits: np.ndarray, rel_tol: float) -> dict:
    """Greedy tokens and logits against reference logits over the same inputs.

    ``tokens`` (n,) and ``logits`` (n, V) come from a served generation;
    ``ref_logits`` (n, V) from a reference fed the same tokens.  Logits must
    agree within ``rel_tol`` of the reference's largest |logit|; a token
    may differ from the reference's argmax only where the reference's top
    two logits are within twice that bound (a tie at this precision).
    """
    got = np.asarray(logits, np.float32)
    ref = np.asarray(ref_logits, np.float32)
    tol = rel_tol * max(1.0, float(np.abs(ref).max()))
    top2 = np.sort(ref, axis=-1)[:, -2:]
    tie = top2[:, 1] - top2[:, 0] <= 2 * tol
    mismatch = np.asarray(tokens) != ref.argmax(axis=-1)
    diff = float(np.abs(got - ref).max())
    return {"max_abs_diff": diff, "tol": tol, "positions": len(ref),
            "token_mismatches": int(mismatch.sum()),
            "ok": bool(diff <= tol and not np.any(mismatch & ~tie))}


def check_against_forward(runner: ModelRunner, prompt: np.ndarray,
                          gen: Generation) -> dict:
    """A greedy generation from ``prompt`` against a teacher-forced forward.

    ``runner`` runs ``forward`` over the prompt and the generated tokens;
    its logits at the positions that produced each token are the reference.
    """
    n = gen.tokens.shape[1]
    seq = np.concatenate([prompt, gen.tokens[0, :-1]])[None]
    ref = runner.forward_logits(seq, n)[0]
    logits = np.stack([np.asarray(x)[0] for x in gen.logits])
    return dict(agreement(gen.tokens[0], logits, ref, logit_tol(runner.cfg)),
                finite=gen.finite)


def prefix_agreement(gen: Generation, ref: Generation, rel_tol: float) -> dict:
    """Two greedy generations from the same prompt (row 0 of each).

    Both were fed the same inputs up to and including the first position
    where their tokens part, so they are compared up to there.
    """
    parted = np.flatnonzero(gen.tokens[0] != ref.tokens[0])
    k = int(parted[0]) + 1 if parted.size else gen.tokens.shape[1]
    a = np.stack([np.asarray(x)[0] for x in gen.logits[:k]])
    b = np.stack([np.asarray(x)[0] for x in ref.logits[:k]])
    return dict(agreement(gen.tokens[0, :k], a, b, rel_tol),
                finite=gen.finite and ref.finite,
                same_tokens=not parted.size)


def _plan(args) -> int:
    profiles, provider = load_catalog(args.results)
    rates = {}
    for part in args.rates.split(","):
        arch, r = part.split("=")
        if arch not in profiles:
            raise SystemExit(
                f"{arch}: no decode/prefill record in {args.results} "
                f"(have: {sorted(profiles)})")
        rates[arch.strip()] = float(r)

    cluster = ClusterSpec(accelerator=V5E_POD, n_devices=args.pods)
    sched = ElasticPartitioning(profiles, cluster=cluster, lat=provider)

    print(f"== tpu-let serving plan: {args.pods} pod(s), "
          f"{len(rates)} model(s) ==")
    for arch, prof in sorted(profiles.items()):
        if arch in rates:
            print(f"  {arch:<20} SLO={prof.slo_ms:7.2f} ms  "
                  f"L(32,pod)={provider.latency_ms(prof, 32, 1.0):7.2f} ms  "
                  f"rate={rates[arch]:.0f}/s")
    if args.max_scale:
        lam = sched.max_scale(rates, hi=1 << 16)
        print(f"max schedulable scale: {lam:.1f}x "
              f"(total {lam * sum(rates.values()):.0f} req/s)")
        rates = {m: r * lam * 0.99 for m, r in rates.items()}

    res = sched.schedule(rates)
    print(f"schedulable: {res.schedulable}  unplaced: {res.unplaced}")
    for gpu in res.gpus:
        parts = []
        for let in gpu.lets:
            n_chips = int(round(let.size / 100 * 256))
            if let.is_free:
                parts.append(f"[{let.size}% = {n_chips} chips: free]")
            else:
                ass = "; ".join(
                    f"{a.model} r={a.rate:.0f}/s b={a.batch} "
                    f"duty={a.duty_ms:.1f}ms L={a.est_latency_ms:.1f}ms"
                    for a in let.assignments)
                parts.append(f"[{let.size}% = {n_chips} chips: {ass}]")
        print(f"  pod {gpu.gpu_id}: " + " ".join(parts))
    return 0


def print_run(result: ServeRun) -> None:
    """Placement, per-model results of a served mix, one line each, and
    the window's first-token p90, latest wake and compile count."""
    for gpu in result.placement.gpus:
        for let in gpu.lets:
            if let.assignments:
                print(f"placement {let.size}% of the chip: " + ", ".join(
                    f"{a.model}(b{a.batch},duty {a.duty_ms:.1f}ms)"
                    for a in let.assignments))
    for r in result.runners:
        summary = result.summary(r)
        print(f"model {summary.pop('model')}: " + " ".join(
            f"{k}={v}" for k, v in summary.items()))
    if result.window is not None:
        w = result.window
        print(f"window first_token_p90_ms={w.first_token_p90_ms()} "
              f"wake_late_max_ms={w.wake_late_max_ms()} compiles={w.compiles}")


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="chatglm3-6b,mamba2-780m",
                    help="comma list of architectures to serve")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--results", help="plan mode: dry-run JSONL (single-pod)")
    ap.add_argument("--rates", help="plan mode: comma list arch=req_per_s")
    ap.add_argument("--pods", type=int, default=4)
    ap.add_argument("--max-scale", action="store_true",
                    help="plan mode: report the max schedulable multiple "
                         "of --rates")
    args = ap.parse_args(argv)
    if args.results:
        if not args.rates:
            ap.error("--results needs --rates")
        return _plan(args)

    use_compile_cache(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
    result = run([get_config(a) for a in args.models.split(",")],
                 n_requests=args.requests, seed=args.seed)
    print_run(result)
    return 0 if all(r.output is not None for r in result.requests) else 1


if __name__ == "__main__":
    raise SystemExit(main())
