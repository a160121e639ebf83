"""Training loop: sharded train_step, grad accumulation, fault tolerance.

Fault-tolerance contract (designed for 1000+ nodes, exercised here on the
host mesh):

  * **checkpoint/restart** — atomic-commit checkpoints every
    ``ckpt_every`` steps (async writer); on start, ``Trainer.restore_if_any``
    resumes from the newest commit. Data is step-indexed, so the resumed
    batch stream is bit-identical.
  * **preemption** — SIGTERM triggers a final synchronous checkpoint before
    exit (standard TPU-pod preemption notice handling).
  * **elastic restart** — checkpoints are mesh-agnostic (logical axes);
    restoring onto a different mesh re-shards automatically.
  * **straggler mitigation** — per-step wall-time EWMA; steps slower than
    ``straggler_factor``× the EWMA are logged with their device set so the
    launcher can cordon a slow host (on-pod action is a launcher concern;
    the hook + detection live here).

Distributed-optimization knobs: microbatch gradient accumulation
(``lax.scan``), optional int8 compressed gradient all-reduce
(``dp_compress`` → shard_map path), remat through the attention chunking.
"""

from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.data.pipeline import DataConfig, sharded_batch
from repro.models import registry, schema as schema_lib
from repro.models.config import ModelConfig
from repro.optim import optimizer as opt_lib
from repro.parallel import context as pctx
from repro.parallel import sharding as sh


@dataclasses.dataclass
class TrainConfig:
    model: ModelConfig
    opt: opt_lib.OptConfig
    global_batch: int = 32
    seq_len: int = 256
    microbatches: int = 1
    fsdp: bool = True
    cast_params_bf16: bool = False  # bf16 weight gathers + grad reductions
    dp_compress: bool = False
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 100
    ckpt_async: bool = True
    straggler_factor: float = 3.0
    seed: int = 0


def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    return nll.mean()


def make_loss_fn(arch: registry.Arch, cast_bf16: bool = False,
                 param_sharding=None) -> Callable:
    def loss_fn(params, tokens, embeds=None):
        if cast_bf16:
            # §Perf iteration 3: cast-BEFORE-gather. Casting alone is not
            # enough — XLA will all-gather the f32 master and cast after.
            # Re-asserting the *sharded* layout on the bf16 copy makes the
            # FSDP all-gather move bf16 (2× fewer bytes), and its cotangent
            # becomes a bf16 reduce-scatter instead of an f32 all-reduce.
            def cast(p, s=None):
                if p.dtype != jnp.float32:
                    return p
                pb = p.astype(jnp.bfloat16)
                return pb if s is None else jax.lax.with_sharding_constraint(pb, s)

            if param_sharding is None:
                params = jax.tree.map(cast, params)
            else:
                params = jax.tree.map(cast, params, param_sharding)
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        kw = {}
        if embeds is not None:
            # frontend-stub models: embeddings align with the input tokens
            kw["embeds"] = (embeds[:, :-1]
                            if arch.cfg.family != "encdec" else embeds)
        logits = arch.forward(params, inp, **kw)
        return cross_entropy(logits, tgt)

    return loss_fn


def make_train_step(arch: registry.Arch, tc: TrainConfig,
                    batch_sharding: Optional[NamedSharding] = None,
                    param_sharding=None):
    """jit-able (params, opt_state, tokens) → (params, opt_state, metrics).

    Microbatching: tokens [G, B/G, S] scanned; grads accumulated in f32.
    ``batch_sharding``: sharding of the [B, S] token batch — re-asserted
    after the microbatch reshape (GSPMD propagation loses the batch axis
    through [B,…]→[G,B/G,…] otherwise, silently replicating activations).
    """
    loss_fn = make_loss_fn(arch, cast_bf16=tc.cast_params_bf16,
                           param_sharding=param_sharding)

    def _constrain(x):
        if batch_sharding is None:
            return x
        spec = batch_sharding.spec
        micro_spec = P(None, *spec)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(batch_sharding.mesh, micro_spec))

    def _constrain_grads(grads):
        if param_sharding is None:
            return grads
        # §Perf: pin gradient shardings to the parameter layout so GSPMD
        # emits reduce-scatter (not a replicated all-reduce) for the DP
        # gradient reduction.
        return jax.tree.map(jax.lax.with_sharding_constraint, grads,
                            param_sharding)

    def train_step(params, opt_state, tokens, embeds=None):
        g = tc.microbatches
        if g == 1:  # no accumulation loop — direct fwd/bwd
            loss, grads = jax.value_and_grad(loss_fn)(
                params, tokens, embeds)
            grads = _constrain_grads(grads)
            new_params, new_opt, metrics = opt_lib.update(
                tc.opt, opt_state, params, grads)
            return new_params, new_opt, {"loss": loss, **metrics}

        def micro(carry, xs):
            acc, loss_acc = carry
            toks = xs if embeds is None else xs[0]
            emb = None if embeds is None else xs[1]
            loss, grads = jax.value_and_grad(loss_fn)(params, toks, emb)
            acc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32) / g, acc, grads)
            return (acc, loss_acc + loss / g), None

        acc0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        b = tokens.shape[0]
        toks_g = _constrain(tokens.reshape(g, b // g, *tokens.shape[1:]))
        xs = toks_g if embeds is None else (
            toks_g, _constrain(embeds.reshape(g, b // g, *embeds.shape[1:])))
        (grads, loss), _ = jax.lax.scan(micro, (acc0, 0.0), xs)
        grads = _constrain_grads(grads)
        new_params, new_opt, metrics = opt_lib.update(
            tc.opt, opt_state, params, grads)
        return new_params, new_opt, {"loss": loss, **metrics}

    return train_step


def make_compressed_train_step(arch: registry.Arch, tc: TrainConfig,
                               mesh: Mesh):
    """DP-only train step with int8 gradient all-reduce + error feedback.

    The paper's wide/narrow QoS split, applied to training traffic: bulk
    gradient payloads ride the network as int8 (4× fewer bytes than f32),
    with a scalar pmax agreeing on per-tensor scales (the latency class).
    Requires a pure data-parallel mesh (params replicated) — compose with
    FSDP is future work. Returns (step_fn, init_error_buf_fn); the error
    buffer is part of the training state and must be threaded through.
    """
    from jax import shard_map

    from repro.optim.grad_compression import compress_decompress_psum

    if dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1) != 1:
        raise ValueError("dp_compress requires a data-parallel-only mesh")
    loss_fn = make_loss_fn(arch)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    n_data = 1
    for a, sz in zip(mesh.axis_names, mesh.devices.shape):
        if a in data_axes:
            n_data *= sz

    def step(params, opt_state, err_buf, tokens):
        def local(params, err_buf, toks):
            # err_buf carries a leading data-shard axis (the residual is
            # genuinely per-device state — it is NOT replicated)
            e = jax.tree.map(lambda t: t[0], err_buf)
            loss, g = jax.value_and_grad(loss_fn)(params, toks)
            g_mean, new_e = compress_decompress_psum(g, e, data_axes)
            loss = jax.lax.pmean(loss, data_axes)
            return loss, g_mean, jax.tree.map(lambda t: t[None], new_e)

        spec_rep = jax.tree.map(lambda _: P(), params)
        spec_err = jax.tree.map(lambda _: P(data_axes), params)
        fm = shard_map(
            local, mesh=mesh,
            in_specs=(spec_rep, spec_err, P(*data_axes)),
            out_specs=(P(), spec_rep, spec_err),
        )
        loss, grads, new_err = fm(params, err_buf, tokens)
        new_params, new_opt, metrics = opt_lib.update(
            tc.opt, opt_state, params, grads)
        return new_params, new_opt, new_err, {"loss": loss, **metrics}

    def init_err(params):
        return jax.tree.map(
            lambda x: jnp.zeros((n_data, *x.shape), jnp.float32), params)

    return step, init_err


class Trainer:
    def __init__(self, tc: TrainConfig, mesh: Mesh):
        self.tc = tc
        self.mesh = mesh
        self.arch = registry.build(tc.model)
        self.rules = sh.train_rules(fsdp=tc.fsdp)
        self.schema = self.arch.schema()
        self.p_axes = schema_lib.logical_axes(self.schema)
        self.p_shard = self.rules.tree_sharding(self.p_axes, mesh)
        self.o_axes = opt_lib.state_axes(tc.opt, self.p_axes)
        self.data_cfg = DataConfig(
            vocab=tc.model.vocab, seq_len=tc.seq_len,
            global_batch=tc.global_batch, seed=tc.seed)
        self._preempted = False
        self.step = 0
        self._step_ewma = None

        init = lambda key: schema_lib.init_params(self.schema, key)
        with mesh:
            self.params = jax.jit(init, out_shardings=self.p_shard)(
                jax.random.key(tc.seed))
            self.o_shard = self.rules.tree_sharding(self.o_axes, mesh)
            self.opt_state = jax.jit(
                lambda p: opt_lib.init(tc.opt, p),
                out_shardings=self.o_shard)(self.params)
            batch_spec = P(self.rules.mesh_axes("batch", mesh))
            self.batch_sharding = NamedSharding(mesh, batch_spec)
            self._step_fn = jax.jit(
                make_train_step(self.arch, tc, self.batch_sharding),
                in_shardings=(self.p_shard, self.o_shard, self.batch_sharding),
                out_shardings=(self.p_shard, self.o_shard, None),
                donate_argnums=(0, 1),
            )

    # -- fault tolerance ----------------------------------------------------

    def install_preemption_handler(self):
        def handler(signum, frame):
            self._preempted = True

        signal.signal(signal.SIGTERM, handler)

    def save(self, sync: bool = False):
        from repro.train import checkpointing as ckpt

        if not self.tc.ckpt_dir:
            return
        tree = {"params": self.params, "opt": self.opt_state}
        ckpt.save(self.tc.ckpt_dir, self.step, tree,
                  meta={"arch": self.tc.model.name},
                  async_write=self.tc.ckpt_async and not sync)

    def restore_if_any(self) -> bool:
        from repro.train import checkpointing as ckpt

        if not self.tc.ckpt_dir:
            return False
        step = ckpt.latest_step(self.tc.ckpt_dir)
        if step is None:
            return False
        like = {"params": jax.device_get(self.params),
                "opt": jax.device_get(self.opt_state)}
        shard = {"params": self.p_shard, "opt": self.o_shard}
        tree = ckpt.restore(self.tc.ckpt_dir, step, like, shard)
        self.params, self.opt_state = tree["params"], tree["opt"]
        self.step = step
        return True

    # -- main loop -----------------------------------------------------------

    def run(self, num_steps: int, log_every: int = 10,
            corpus=None) -> list:
        history = []
        act = sh.activation_rules(self.rules)
        with self.mesh, pctx.activation_sharding(self.mesh, act):
            while self.step < num_steps and not self._preempted:
                t0 = time.perf_counter()
                tokens = sharded_batch(
                    self.data_cfg, self.step, self.batch_sharding, corpus)
                self.params, self.opt_state, metrics = self._step_fn(
                    self.params, self.opt_state, tokens)
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                self._track_stragglers(dt)
                self.step += 1
                if self.step % log_every == 0 or self.step == num_steps:
                    history.append({"step": self.step, "loss": loss,
                                    "sec": dt})
                if self.tc.ckpt_dir and self.step % self.tc.ckpt_every == 0:
                    self.save()
            if self._preempted:
                self.save(sync=True)  # preemption: final synchronous commit
        return history

    def _track_stragglers(self, dt: float):
        if self._step_ewma is None:
            self._step_ewma = dt
            return
        if dt > self.tc.straggler_factor * self._step_ewma:
            print(f"[straggler] step {self.step}: {dt:.3f}s vs "
                  f"EWMA {self._step_ewma:.3f}s — flagging host set "
                  f"{sorted({d.process_index for d in self.mesh.devices.flat})}")
        self._step_ewma = 0.9 * self._step_ewma + 0.1 * dt
