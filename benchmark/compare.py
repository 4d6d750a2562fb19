"""The comparison that decides ``correct``.

What the timed path produced in its first three optimizer steps (the host
batches it was fed, each step's loss, the first gradient's norms as the
optimizer got it, the parameters' change after the three) against the plain
reference following the same three steps from the same seed. The reference
makes its own weights, its own old log-probabilities, values and rewards, and
reads of the program's records only the tokens and the numbers compared.

Numbers read. The cell's file gives a limit to each one it compares; one it
gives none is read and printed under ``info.not_compared`` (PERF.md says which
and why):

- ``loss_gap_1..3``: each step's total loss against the reference's, as a
  share of the size of the reference's two terms (|policy| + value): the total
  itself crosses zero, the policy term being negative as often as not.
- ``grad_gap``: the worst leaf's gap between the program's gradient norm and
  the reference's, against the reference's norm of that leaf or of the median
  leaf, whichever is larger.
- ``update_gap``: the same of the parameters' change after three steps, over
  the leaves whose reference gradient is at least a thousandth of the median
  leaf's (the others move under Adam by round-off alone).
- ``rollout_gap``: the widest gap by which a generated token's logit lies
  below the reference's best at its position (the traffic samples at a
  temperature of 1e-4, so every token is the greedy one but for ties).
- ``score_logprobs_gap``, ``score_values_gap``, ``score_rewards_gap``: the
  widest gap between what the scoring forward stored for a response token
  (policy log-probability, value, KL-penalised reward) and the reference's own.
  The policy is its own reference model until the first update, so the
  reference's reward has no KL term, and a reference model's forward that
  differs from the policy's shows as a reward gap of ``init_kl_coef`` times it.
"""

from typing import Any, Dict, Tuple

import numpy as np

from benchmark.harness import flat_name

EOS_TOKEN, BYTE_OFFSET = 2, 3  # the program's byte tokenizer
NEGLIGIBLE_GRADIENT = 1e-3  # of the median leaf's
REFERENCE_BLOCK_ROWS = 4  # rows the reference takes at a time, so that it fits


def _worst_leaf_gap(program: Dict[str, float], ref: Dict[str, float], leaves) -> Tuple[float, str]:
    median = float(np.median([ref[k] for k in leaves]))
    worst, where = 0.0, ""
    for k in leaves:
        gap = abs(program[k] - ref[k]) / max(ref[k], median, 1e-30)
        if not gap <= worst:  # NaN too
            worst, where = gap, k
    return float(worst), where


def _flat_norms(norms: Dict[str, Any]) -> Dict[str, float]:
    out = {}
    for name, value in norms.items():
        value = np.asarray(value)
        if name.startswith("h."):
            out.update({flat_name(name, i): float(v) for i, v in enumerate(value)})
        else:
            out[name] = float(value)
    return out


def readings(session) -> Tuple[Dict[str, float], Dict[str, Any]]:
    """(the numbers compared, information beside them) for a closed session
    whose program state has been freed."""
    import jax
    import jax.numpy as jnp

    cell, config, seed = session.cell, session.config, session.seed
    reference = session.family.reference
    steps = session.CHECKED_STEPS
    if len(session.batches) < steps or session.grad_norms is None or session.update_norms is None:
        raise RuntimeError("the window closed before three optimizer steps were recorded")
    ppo = cell["ppo"]
    first = session.batches[0]
    B, P = first["query_tensors"].shape
    R = first["response_tensors"].shape[1]
    N = cell["new_tokens"]
    num_mb = max(1, cell["batch_size"] // (cell["minibatch_size"] or cell["batch_size"]))
    block_rows = min(REFERENCE_BLOCK_ROWS, B // num_mb)

    with jax.default_matmul_precision("highest"):
        weights = reference.init_weights(config, seed)
        window = jax.jit(
            lambda w, seq, mask: reference.response_window(w, config, seq, mask, P, R, EOS_TOKEN)
        )
        info: Dict[str, Any] = {}
        prepared, rollout_gap = [], 0.0
        score_gaps = {"logprobs": 0.0, "values": 0.0, "rewards": 0.0}
        for batch in session.batches[:steps]:
            seq = np.concatenate([batch["query_tensors"], batch["response_tensors"]], axis=1).astype(np.int32)
            mask = np.concatenate([batch["attention_mask"], batch["response_mask"]], axis=1).astype(np.int32)
            old = {"logprobs": [], "values": []}
            for i in range(0, B, block_rows):
                lp, v, gap = window(weights, seq[i : i + block_rows], mask[i : i + block_rows])
                old["logprobs"].append(np.asarray(lp))
                old["values"].append(np.asarray(v))
                # generated tokens only: the trainer re-appends eos after them
                rollout_gap = max(rollout_gap, float(np.asarray(gap)[:, :N].max()))
            rmask = batch["response_mask"].astype(np.float32)
            old = {k: np.concatenate(v) * rmask for k, v in old.items()}
            # the policy is its own reference until the first update: no KL term
            rewards = np.zeros((B, R), np.float32)
            for row in range(B):
                ids = batch["query_tensors"][row][batch["attention_mask"][row] > 0]
                prompt = bytes((ids - BYTE_OFFSET).astype(np.uint8)).decode("ascii")
                score = np.clip(session.scores[prompt], -ppo["cliprange_reward"], ppo["cliprange_reward"])
                rewards[row, int(rmask[row].sum()) - 1] = score
            old["rewards"] = rewards
            for k in score_gaps:
                score_gaps[k] = max(score_gaps[k], float(np.abs(batch[k] * rmask - old[k]).max()))
            prepared.append((seq, mask, old["logprobs"], old["values"], old["rewards"]))

        hp = {k: float(ppo[k]) for k in (
            "lr", "b1", "b2", "eps", "weight_decay", "gamma", "lam", "cliprange", "cliprange_value", "vf_coef")}
        step = reference.make_train_step(config, hp, P, R, num_mb, block_rows)
        opt = reference.init_opt(weights)
        ref_losses, ref_grad_norms = [], None
        for i, batch in enumerate(prepared):
            weights, opt, (pg, vf), grads = step(weights, opt, tuple(jnp.asarray(x) for x in batch))
            ref_losses.append((float(pg), float(vf)))
            if i == 0:
                ref_grad_norms = _flat_norms(jax.device_get(reference.leaf_norms(grads)))
            del grads
        start = reference.init_weights(config, seed)
        delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(weights, start)
        ref_update_norms = _flat_norms(jax.device_get(reference.leaf_norms(delta)))
        del weights, opt, start, delta

    numbers = {
        f"loss_gap_{i + 1}": abs(session.losses[i] - sum(ref_losses[i])) / max(sum(map(abs, ref_losses[i])), 1e-30)
        for i in range(steps)
    }
    leaves = sorted(ref_grad_norms)
    numbers["grad_gap"], info["grad_gap_leaf"] = _worst_leaf_gap(session.grad_norms, ref_grad_norms, leaves)
    median_grad = float(np.median([ref_grad_norms[k] for k in leaves]))
    moved = [k for k in leaves if ref_grad_norms[k] >= NEGLIGIBLE_GRADIENT * median_grad]
    numbers["update_gap"], info["update_gap_leaf"] = _worst_leaf_gap(
        session.update_norms, ref_update_norms, moved)
    numbers["rollout_gap"] = rollout_gap
    numbers.update({f"score_{k}_gap": v for k, v in score_gaps.items()})
    info["leaves_left_out_of_update_gap"] = len(leaves) - len(moved)
    info["losses"] = {"program": session.losses[:steps], "reference": [sum(l) for l in ref_losses]}
    return numbers, info


def decide(numbers: Dict[str, float], limits: Dict[str, float]) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """``correct`` and each number compared beside its limit. The cell's file
    names the numbers that are compared, each with the limit its two readings
    gave (PERF.md has them); a reading the file gives no limit is information,
    and a limit for a number that was not read is an error."""
    unread = set(limits) - set(numbers)
    if unread or not limits:
        raise KeyError(f"the cell's file limits {sorted(unread)}, which the comparison does not read")
    compared = {name: {"value": numbers[name], "limit": limits[name]} for name in numbers if name in limits}
    correct = all(c["value"] <= c["limit"] for c in compared.values())  # NaN fails
    return correct, compared


def compared_lines(compared: Dict[str, Dict[str, float]]) -> str:
    return "\n".join(
        f"compared {name} value {c['value']:.6g} limit {c['limit']:.6g}" for name, c in compared.items()
    )
