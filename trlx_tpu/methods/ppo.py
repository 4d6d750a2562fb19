"""PPO method: hyperparameters, KL controllers, GAE, and the clipped surrogate loss.

Functional parity with the reference's ``PPOConfig``
(`/root/reference/trlx/models/modeling_ppo.py:32-238`): same hyperparameter surface,
same GAE math (`get_advantages_and_returns`, :136-173), same clipped policy+value loss
and stat names (:175-238), and the same Adaptive/Fixed KL controllers (:35-67). The
implementation is TPU-first: GAE is a reverse ``lax.scan`` (not a Python loop), all
ragged response lengths are handled with masks at fixed shapes, and whitening reduces
over the global sharded batch (XLA inserts the cross-device collectives).
"""

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.analysis.ir.entrypoints import EntryArtifacts, register_entrypoint
from trlx_tpu.data.method_configs import MethodConfig, register_method
from trlx_tpu.utils.modeling import masked_mean, whiten


class AdaptiveKLController:
    """Adaptive KL coefficient per https://arxiv.org/abs/1909.08593 §2.2
    (parity: modeling_ppo.py:35-53)."""

    def __init__(self, init_kl_coef: float, target: float, horizon: int):
        self.value = init_kl_coef
        self.target = target
        self.horizon = horizon

    def update(self, current: float, n_steps: int):
        # host-side scalar math: no device op / sync per step
        proportional_error = min(0.2, max(-0.2, float(current) / self.target - 1))
        self.value *= 1 + proportional_error * n_steps / self.horizon


class FixedKLController:
    """Constant KL coefficient (parity: modeling_ppo.py:56-67)."""

    def __init__(self, kl_coef: float):
        self.value = kl_coef

    def update(self, current: float, n_steps: int):
        pass


def gae_advantages_and_returns(
    values: jnp.ndarray,
    rewards: jnp.ndarray,
    mask: jnp.ndarray,
    gamma: float,
    lam: float,
    use_whitening: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Generalized Advantage Estimation over the response window.

    Shapes: values/rewards/mask are [B, T] over response tokens (mask 1 where a real
    response token exists). Equivalent to the reference's reverse Python loop
    (modeling_ppo.py:136-173) but expressed as a reverse ``lax.scan`` so it compiles
    to one fused kernel. Positions past a sample's response end contribute nothing:
    bootstrap values and deltas are masked.
    """
    mask = mask.astype(values.dtype)
    values = values * mask
    rewards = rewards * mask
    next_values = jnp.concatenate([values[:, 1:], jnp.zeros_like(values[:, :1])], axis=1)
    next_mask = jnp.concatenate([mask[:, 1:], jnp.zeros_like(mask[:, :1])], axis=1)
    deltas = rewards + gamma * next_values * next_mask - values

    def step(carry, xs):
        delta_t, m_next = xs
        carry = delta_t + gamma * lam * m_next * carry
        return carry, carry

    # scan over time, reversed; carry shape [B]
    _, adv_rev = jax.lax.scan(
        step,
        jnp.zeros_like(deltas[:, 0]),
        (deltas.T[::-1], next_mask.T[::-1]),
    )
    advantages = adv_rev[::-1].T * mask
    returns = advantages + values
    if use_whitening:
        advantages = whiten(advantages, mask=mask) * mask
    return jax.lax.stop_gradient(advantages), jax.lax.stop_gradient(returns)


@register_method
@dataclass
class PPOConfig(MethodConfig):
    """PPO hyperparameters (parity: modeling_ppo.py:70-134; same field names).

    :param num_rollouts: rollouts collected per experience phase.
    :param chunk_size: prompts per generation batch during rollout.
    :param ppo_epochs: optimization epochs per experience batch.
    :param init_kl_coef / target / horizon: KL controller (adaptive if target set).
    :param gamma / lam: GAE discounting.
    :param cliprange / cliprange_value / vf_coef: clipped-loss coefficients.
    :param scale_reward: None | "ref" | "running" reward scaling.
    :param cliprange_reward: clip scores to ±value before KL assembly.
    :param gen_kwargs / gen_experience_kwargs: generation settings (eval / rollout).
    :param num_value_layers_unfrozen: depth of the separate value branch (0 = head only).
    """

    name: str = "PPOConfig"
    ppo_epochs: int = 4
    num_rollouts: int = 128
    chunk_size: int = 128
    init_kl_coef: float = 0.05
    target: Optional[float] = 6.0
    horizon: int = 10000
    gamma: float = 1.0
    lam: float = 0.95
    cliprange: float = 0.2
    cliprange_value: float = 0.2
    vf_coef: float = 1.0
    scale_reward: Optional[str] = "ignored"
    ref_mean: Optional[float] = None
    ref_std: Optional[float] = None
    cliprange_reward: float = 10.0
    gen_kwargs: Dict[str, Any] = field(default_factory=lambda: dict(max_new_tokens=16))
    gen_experience_kwargs: Optional[Dict[str, Any]] = None
    num_value_layers_unfrozen: int = 0
    # overlap reward_fn scoring of chunk i with generation of chunk i+1 during
    # make_experience (double-buffer; worthwhile when the reward model is served
    # remotely — the RPC round-trip hides behind device work). reward_fn then
    # runs on a worker thread, so it must be thread-safe.
    overlap_reward_scoring: bool = False
    # prompts per *generation* device batch during make_experience (defaults to
    # chunk_size). Decode is bandwidth-bound on the weights — every step streams
    # all parameters regardless of batch — so the decode batch wants to be as
    # wide as memory allows, independently of the reward/scoring chunk (the
    # benchmark's cells decode 128 and 64 rows and score in chunks of 32).
    decode_batch_size: Optional[int] = None

    def kl_controller(self):
        if self.target is not None:
            return AdaptiveKLController(self.init_kl_coef, self.target, self.horizon)
        return FixedKLController(self.init_kl_coef)

    def get_advantages_and_returns(self, values, rewards, mask, use_whitening: bool = True):
        return gae_advantages_and_returns(values, rewards, mask, self.gamma, self.lam, use_whitening)

    def loss(
        self,
        logprobs: jnp.ndarray,
        values: jnp.ndarray,
        old_logprobs: jnp.ndarray,
        old_values: jnp.ndarray,
        advantages: jnp.ndarray,
        returns: jnp.ndarray,
        mask: jnp.ndarray,
        staleness: Optional[jnp.ndarray] = None,
        is_ratio_clip: Optional[float] = None,
    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """Clipped PPO policy + value loss with the reference's stats dict
        (modeling_ppo.py:175-238). All inputs are [B, T_resp]-shaped and masked.

        With ``staleness`` ([B] policy-version lag from the async rollout
        engine) and ``is_ratio_clip`` both set, the policy term of stale
        samples is reweighted by clipped per-token importance weights against
        the behavior-policy ``old_logprobs`` (docs/rollout.md). Weights are
        exactly 1.0 at staleness 0, keeping on-policy losses bitwise-identical
        to the vanilla path."""
        mask = mask.astype(values.dtype)
        # pin the float hyperparameters to concrete dtypes once (SH002): as
        # bare Python floats each use would trace as a weak_type scalar,
        # splitting the jit cache on weak_type and letting promotion drift on
        # bf16 operands
        cliprange = jnp.asarray(self.cliprange, logprobs.dtype)
        cliprange_value = jnp.asarray(self.cliprange_value, values.dtype)
        vf_coef = jnp.asarray(self.vf_coef, jnp.float32)
        # every loss accumulation pins dtype=float32: operands may be bf16 on
        # TPU, and a sequence-length sum in bf16 loses the low bits of exactly
        # the small per-token terms PPO clips on (JX007 discipline)
        n = jnp.maximum(mask.sum(dtype=jnp.float32), 1.0)

        values_clipped = jnp.clip(
            values, old_values - cliprange_value, old_values + cliprange_value
        )
        vf_loss1 = (values - returns) ** 2
        vf_loss2 = (values_clipped - returns) ** 2
        vf_loss = 0.5 * jnp.sum(jnp.maximum(vf_loss1, vf_loss2) * mask, dtype=jnp.float32) / n
        vf_clipfrac = jnp.sum((vf_loss2 > vf_loss1).astype(mask.dtype) * mask, dtype=jnp.float32) / n

        log_ratio = (logprobs - old_logprobs) * mask
        ratio = jnp.exp(log_ratio)
        # k3 estimator of approximate KL: mean(exp(-lr) - 1 + lr)
        approx_kl = jnp.sum((jnp.exp(-log_ratio) - 1.0 + log_ratio) * mask, dtype=jnp.float32) / n

        is_weights = None
        if staleness is not None and is_ratio_clip is not None:
            from trlx_tpu.rollout.staleness import staleness_importance_weights

            # reweight the surrogate's advantages (w > 0 commutes with the
            # clipped max below); stop-gradient inside keeps this a fixed
            # per-token correction, not a second policy-gradient path
            is_weights = staleness_importance_weights(log_ratio, staleness, is_ratio_clip)
            advantages = advantages * is_weights

        pg_loss1 = -advantages * ratio
        pg_loss2 = -advantages * jnp.clip(ratio, 1.0 - cliprange, 1.0 + cliprange)
        pg_loss = jnp.sum(jnp.maximum(pg_loss1, pg_loss2) * mask, dtype=jnp.float32) / n
        pg_clipfrac = jnp.sum((pg_loss2 > pg_loss1).astype(mask.dtype) * mask, dtype=jnp.float32) / n

        loss = pg_loss + vf_coef * vf_loss

        stats = dict(
            losses=dict(total_loss=loss, policy_loss=pg_loss, value_loss=vf_loss),
            values=dict(
                get_tensor_stats=dict(
                    mean=masked_mean(values, mask),
                    min=jnp.min(jnp.where(mask > 0, values, jnp.inf)),
                    max=jnp.max(jnp.where(mask > 0, values, -jnp.inf)),
                    std=jnp.sqrt(masked_mean((values - masked_mean(values, mask)) ** 2, mask)),
                ),
                values_error=jnp.sum(((values - returns) * mask) ** 2, dtype=jnp.float32) / n,
                clipfrac=vf_clipfrac,
            ),
            old_values=dict(mean=masked_mean(old_values, mask)),
            returns=dict(
                mean=masked_mean(returns, mask),
                std=jnp.sqrt(masked_mean((returns - masked_mean(returns, mask)) ** 2, mask)),
            ),
            policy=dict(approx_kl=approx_kl, clipfrac=pg_clipfrac),
            ratio=jnp.sum(ratio * mask, dtype=jnp.float32) / n,
            padding_percentage=1.0 - n / mask.size,
        )
        if is_weights is not None:
            stats["staleness"] = dict(
                mean=jnp.mean(staleness.astype(jnp.float32)),
                max=jnp.max(staleness),
                is_weight_mean=jnp.sum(is_weights * mask, dtype=jnp.float32) / n,
            )
        return loss, stats


# -- AOT audit surface (graftcheck-ir) ----------------------------------------


@register_entrypoint("ppo_train_step", specs=("small",))
def build_ppo_train_step(spec: str, mesh) -> EntryArtifacts:
    """The PPO learner step as graftcheck-ir audits it: the same
    loss/grad-accum-scan/optax-update construction as
    ``PPOTrainer._get_train_step`` + ``MeshRLTrainer.make_grad_accum_step``,
    over fully abstract sharded inputs (nothing materialized — the
    ``scripts/scale_proof.py`` blueprint at audit shapes).

    ``TRLX_IR_SEED_REGRESSION`` injects a deliberate defect (``f32_upcast``:
    an f32 logit matmul IR001 must flag; ``allgather``: a replication
    constraint whose all-gather must break the IR005 budget) so CI can prove
    the gate fails closed.
    """
    return _build_train_step(spec, mesh, PPOConfig())


def _build_train_step(spec: str, mesh, method) -> EntryArtifacts:
    """The shared audit-shape learner-step construction behind the
    ``ppo_train_step`` and ``grpo_train_step`` entrypoints — GRPO inherits
    PPO's step plumbing wholesale (methods/grpo.py), so the audit surface is
    one builder parameterized by the method, not two drifting copies."""
    import os

    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from trlx_tpu.data.ppo_types import PPORLBatch
    from trlx_tpu.models.policy import CausalLMWithValueHead, head_of
    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.parallel.mesh import BATCH_AXES
    from trlx_tpu.parallel.sharding import make_param_shardings, make_state_shardings
    from trlx_tpu.utils.modeling import response_logprobs

    dims = {"small": dict(hidden=64, layers=2, heads=4, vocab=256, B=8, P=24, R=8)}[spec]
    model_config = PRESETS["gpt2"].replace(
        vocab_size=dims["vocab"], hidden_size=dims["hidden"],
        num_layers=dims["layers"], num_heads=dims["heads"],
        intermediate_size=4 * dims["hidden"], max_position_embeddings=64,
        param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
    )
    module = CausalLMWithValueHead(model_config)
    seed_regression = os.environ.get("TRLX_IR_SEED_REGRESSION", "")

    params_shape = jax.eval_shape(
        lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), jnp.ones((1, 2), jnp.int32)
        )
    )["params"]
    abs_params = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        params_shape, make_param_shardings(params_shape, mesh),
    )
    tx = optax.adamw(1e-5)
    opt_shapes = jax.eval_shape(tx.init, abs_params)
    abs_opt = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        opt_shapes, make_state_shardings(opt_shapes, mesh),
    )

    B, P, R = dims["B"], dims["P"], dims["R"]
    bsh = NamedSharding(mesh, PartitionSpec(BATCH_AXES, None))

    def babs(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=bsh)

    abs_batch = PPORLBatch(
        query_tensors=babs((B, P), jnp.int32),
        response_tensors=babs((B, R), jnp.int32),
        logprobs=babs((B, R), jnp.float32),
        values=babs((B, R), jnp.float32),
        rewards=babs((B, R), jnp.float32),
        attention_mask=babs((B, P), jnp.int32),
        response_mask=babs((B, R), jnp.int32),
    )
    num_mb = 2

    def loss_fn(params, mb):
        seq = jnp.concatenate([mb.query_tensors, mb.response_tensors], axis=1)
        mask = jnp.concatenate([mb.attention_mask, mb.response_mask], axis=1)
        hidden, values_pred, _, _ = module.apply({"params": params}, seq, mask, with_head=False)
        if seed_regression == "allgather":
            # audit seed: replicating the sharded hidden states forces an
            # all-gather the committed budget does not contain
            hidden = jax.lax.with_sharding_constraint(
                hidden, NamedSharding(mesh, PartitionSpec())
            )
        start = mb.query_tensors.shape[1] - 1
        logprobs = response_logprobs(hidden, head_of(module, params), seq, start, R)
        values_pred = values_pred[:, start:start + R].astype(jnp.float32)
        advantages, returns = method.get_advantages_and_returns(
            mb.values, mb.rewards, mb.response_mask
        )
        loss, _ = method.loss(
            logprobs, values_pred, mb.logprobs, mb.values, advantages, returns,
            mb.response_mask,
        )
        if seed_regression == "f32_upcast":
            # audit seed: a heavy f32 matmul inside the bf16-declared step
            hidden32 = hidden.astype(jnp.float32)
            probe = jnp.einsum("btd,bsd->ts", hidden32, hidden32)
            loss = loss + 0.0 * jnp.sum(probe, dtype=jnp.float32)
        return loss

    def train_step(params, opt_state, batch):
        mbs = jax.tree.map(
            lambda x: x.reshape((num_mb, x.shape[0] // num_mb) + x.shape[1:]), batch
        )

        def body(grads_acc, mb):
            grads = jax.grad(loss_fn)(params, mb)
            return jax.tree.map(jnp.add, grads_acc, grads), None

        grads, _ = jax.lax.scan(body, jax.tree.map(jnp.zeros_like, params), mbs)
        grads = jax.tree.map(lambda g: g / num_mb, grads)
        updates, new_opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt_state

    return EntryArtifacts(
        fn=train_step,
        args=(abs_params, abs_opt, abs_batch),
        donate_argnums=(0, 1),
        compute_dtype="bfloat16",
        # the value head's output Dense is deliberately f32 (MLPHead.fc_out):
        # 1 forward + 2 backward dots per step, and no more
        f32_allow=frozenset({"dot_general:3"}),
        meta=dict(batch=B, prompt=P, response=R, num_microbatches=num_mb),
    )


def _ppo_audit_loss_fn(module, method, mesh, R: int):
    """The audit-shape PPO loss shared by the overlap entrypoints: same
    construction as ``build_ppo_train_step``'s, minus the seeds (the overlap
    seed lives in ``parallel/fsdp.py``'s step builder, not the loss)."""
    from trlx_tpu.models.policy import head_of
    from trlx_tpu.utils.modeling import response_logprobs

    def loss_fn(params, mb):
        seq = jnp.concatenate([mb.query_tensors, mb.response_tensors], axis=1)
        mask = jnp.concatenate([mb.attention_mask, mb.response_mask], axis=1)
        hidden, values_pred, _, _ = module.apply({"params": params}, seq, mask, with_head=False)
        start = mb.query_tensors.shape[1] - 1
        logprobs = response_logprobs(hidden, head_of(module, params), seq, start, R)
        values_pred = values_pred[:, start:start + R].astype(jnp.float32)
        advantages, returns = method.get_advantages_and_returns(
            mb.values, mb.rewards, mb.response_mask
        )
        loss, _ = method.loss(
            logprobs, values_pred, mb.logprobs, mb.values, advantages, returns,
            mb.response_mask,
        )
        return loss

    return loss_fn


@register_entrypoint(
    "ppo_train_step_overlap",
    specs=("small",),
    mesh={"data": 2, "fsdp": 2, "pipe": 1, "model": 1},
)
def build_ppo_train_step_overlap(spec: str, mesh) -> EntryArtifacts:
    """The overlapped-collective FSDP learner step (``train.learner_overlap``,
    ``parallel/fsdp.py``) as graftcheck-ir audits it: explicit shard_map
    collectives — per-leaf parameter all-gather in the forward, whose AD
    transpose reduce-scatters the gradient per-leaf during the backward —
    with a gradient-shard accumulation carry and a ZeRO-sharded optimizer
    update. The committed IR005 budget for this entry must show
    ``reduce-scatter:fsdp`` / ``all-gather:fsdp`` and NO ``all-reduce:fsdp``;
    ``TRLX_IR_SEED_REGRESSION=allreduce_under_fsdp`` (handled by the step
    builder) restores the full-gradient all-reduce so CI can prove the budget
    rejects it. Audits on a pure data/fsdp mesh — the overlap path's
    requirement (``fsdp.can_overlap``).
    """
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from trlx_tpu.data.ppo_types import PPORLBatch
    from trlx_tpu.models.policy import CausalLMWithValueHead
    from trlx_tpu.models.presets import PRESETS
    from trlx_tpu.parallel import fsdp as fsdp_lib
    from trlx_tpu.parallel.mesh import BATCH_AXES

    dims = {"small": dict(hidden=64, layers=2, heads=4, vocab=256, B=8, P=24, R=8)}[spec]
    model_config = PRESETS["gpt2"].replace(
        vocab_size=dims["vocab"], hidden_size=dims["hidden"],
        num_layers=dims["layers"], num_heads=dims["heads"],
        intermediate_size=4 * dims["hidden"], max_position_embeddings=64,
        param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
    )
    module = CausalLMWithValueHead(model_config)
    method = PPOConfig()

    params_shape = jax.eval_shape(
        lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2), jnp.int32), jnp.ones((1, 2), jnp.int32)
        )
    )["params"]
    tx = optax.adamw(1e-5)
    specs = fsdp_lib.make_overlap_specs(params_shape, tx, mesh)
    abs_params = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=NamedSharding(mesh, s)),
        params_shape, specs.param_specs,
    )
    abs_opt = fsdp_lib.global_state_struct(specs, mesh)

    B, P, R = dims["B"], dims["P"], dims["R"]
    bsh = NamedSharding(mesh, PartitionSpec(BATCH_AXES, None))

    def babs(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=bsh)

    abs_batch = PPORLBatch(
        query_tensors=babs((B, P), jnp.int32),
        response_tensors=babs((B, R), jnp.int32),
        logprobs=babs((B, R), jnp.float32),
        values=babs((B, R), jnp.float32),
        rewards=babs((B, R), jnp.float32),
        attention_mask=babs((B, P), jnp.int32),
        response_mask=babs((B, R), jnp.int32),
    )
    num_mb = 2
    loss_fn = _ppo_audit_loss_fn(module, method, mesh, R)
    step = fsdp_lib.make_overlapped_grad_accum_step(
        loss_fn, tx, specs, mesh, num_mb, has_aux=False, max_grad_norm=1.0,
    )

    def train_step(params, opt_state, batch):
        new_params, new_opt, _ = step(params, opt_state, batch)
        return new_params, new_opt

    return EntryArtifacts(
        fn=train_step,
        args=(abs_params, abs_opt, abs_batch),
        donate_argnums=(0, 1),
        compute_dtype="bfloat16",
        f32_allow=frozenset({"dot_general:3"}),
        meta=dict(
            batch=B, prompt=P, response=R, num_microbatches=num_mb,
            overlap=True, sharded_opt_state=True,
        ),
    )


@register_entrypoint(
    "ppo_train_step_unsharded_opt",
    specs=("small",),
    mesh={"data": 2, "fsdp": 2, "pipe": 1, "model": 1},
)
def build_ppo_train_step_unsharded_opt(spec: str, mesh) -> EntryArtifacts:
    """Memory comparator for the overlap entry (IR006): the plain GSPMD step
    with deliberately REPLICATED optimizer state, on the same pure data/fsdp
    mesh as ``ppo_train_step_overlap``. The committed budget pins both
    entries' ``memory_bytes``; the overlap entry (sharded state + shard-local
    update) must stay strictly below this one — asserted by
    ``tests/test_learner_overlap.py`` against the committed budget and
    re-checked on every regeneration.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    art = build_ppo_train_step(spec, mesh)
    repl = NamedSharding(mesh, PartitionSpec())
    abs_params, abs_opt, abs_batch = art.args
    abs_opt = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=repl), abs_opt
    )
    return EntryArtifacts(
        fn=art.fn,
        args=(abs_params, abs_opt, abs_batch),
        donate_argnums=art.donate_argnums,
        compute_dtype=art.compute_dtype,
        f32_allow=art.f32_allow,
        meta=dict(art.meta, unsharded_opt_state=True),
    )
