"""GenerationClient: the seam between rollout production and the engine.

Two consumption styles over one :class:`ServingEngine`:

- :meth:`generate_batch` — the rollout path. Takes a ragged batch of prompt
  arrays and returns ``(sequences [B, P+N], response_mask [B, N], P)`` in the
  exact shape/semantics contract of ``MeshRLTrainer.generate`` (left-padded
  prompts to the shared length bucket, pad after eos, mask 1 on generated
  tokens up to and including eos), so ``decode``/scoring/quarantine downstream
  are untouched when ``train.serving`` is enabled.
- :meth:`submit` / :meth:`stream` / :meth:`cancel` — the request API for
  non-rollout sampling traffic: tokens stream out as the engine decodes them,
  and a cancelled request releases its blocks on the next admission round.

The client serializes engine stepping: concurrent ``generate_batch`` /
``stream`` callers interleave their requests into the same continuous batch
(that is the point), with one caller driving the device at a time.
"""

import threading
import time
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from trlx_tpu.obs.flight import flight
from trlx_tpu.ops.generation import LENGTH_BUCKETS, pad_to_bucket
from trlx_tpu.serving.engine import ServingEngine
from trlx_tpu.serving.policy import (
    EngineStoppedError,
    RequestExpiredError,
    RequestShedError,
)
from trlx_tpu.serving.scheduler import FINISH_DEADLINE, FINISH_SHED, Request


class GenerationClient:
    def __init__(self, engine: ServingEngine):
        # ``engine`` may also be a ServingSupervisor — same surface, with
        # crashes absorbed into supervised restarts instead of propagating
        self.engine = engine
        self._step_lock = threading.Lock()

    # -- request API ---------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        stop_sequences: Sequence[Sequence[int]] = (),
        deadline_s: Optional[float] = None,
        tenant_id: Optional[str] = None,
    ) -> int:
        return self.engine.submit(
            prompt, max_new_tokens, stop_sequences=stop_sequences,
            deadline_s=deadline_s, tenant_id=tenant_id,
        )

    def cancel(self, uid: int) -> bool:
        return self.engine.cancel(uid)

    @property
    def policy_version(self) -> int:
        """Broadcast version the engine currently serves (islands mode;
        -1 outside it). The producer stamps its rollout stats with this —
        the *behavior* policy version as the island actually observed it,
        which may run a round or two ahead of the publisher snapshot the
        producer scored against (the staleness accountant's clipped-IS
        correction absorbs exactly that drift)."""
        return int(getattr(self.engine, "serving_version", -1))

    def _request(self, uid: int) -> Request:
        req = self.engine.scheduler.get_request(uid)
        if req is None:
            raise KeyError(f"unknown request uid {uid}")
        return req

    def _replica_of(self, uid: int) -> Optional[int]:
        """Replica attribution for typed errors: a fleet router knows which
        replica served the uid (``replica_of``); a bare engine/supervisor is
        its own replica (``replica_id``, None outside a fleet)."""
        fn = getattr(self.engine, "replica_of", None)
        if fn is not None:
            return fn(uid)
        return getattr(self.engine, "replica_id", None)

    def stream(self, uid: int) -> Iterator[int]:
        """Yield the request's tokens as the engine produces them, driving
        engine rounds while the request is live. Tokens already decoded when
        the iterator starts are yielded immediately.

        Liveness: the iterator never spins on a request that can no longer
        finish. A shed request raises :class:`RequestShedError` and an
        expired one :class:`RequestExpiredError` (both *after* yielding every
        token decoded before the terminal state — partial output is part of
        the accountable outcome); an engine that drained with the request
        unaccounted raises :class:`EngineStoppedError`. Engine-side failures
        (e.g. a supervised restart budget exhausting mid-stream) propagate
        from ``step()`` instead of being swallowed into an infinite loop."""
        req = self._request(uid)
        sent = 0
        while True:
            gen = req.generated
            while sent < len(gen):
                yield gen[sent]
                sent += 1
            if req.done:
                break
            with self._step_lock:
                if not req.done:
                    self.engine.step()
                    if not req.done and not self.engine.scheduler.has_work:
                        raise EngineStoppedError(
                            f"engine drained with request uid={uid} unaccounted "
                            f"({sent} tokens streamed)",
                            tenant_id=req.tenant_id, slo_class=req.slo_class,
                            replica_id=self._replica_of(uid), uid=uid,
                        )
        for tok in req.generated[sent:]:
            yield tok
        # typed errors carry the request's tenant attribution so callers can
        # bill/alert per tenant without a second lookup (None-free: every
        # request carries at least the default-tenant tags)
        if req.finish_reason == FINISH_SHED:
            raise RequestShedError(
                f"request uid={uid} was shed after {len(req.generated)} tokens",
                tenant_id=req.tenant_id, slo_class=req.slo_class,
                replica_id=self._replica_of(uid), uid=uid,
            )
        if req.finish_reason == FINISH_DEADLINE:
            raise RequestExpiredError(
                f"request uid={uid} expired (deadline_s={req.deadline_s}) "
                f"after {len(req.generated)} tokens",
                tenant_id=req.tenant_id, slo_class=req.slo_class,
                replica_id=self._replica_of(uid), uid=uid,
            )

    # -- rollout path --------------------------------------------------------

    def generate_batch(
        self,
        prompts: List[np.ndarray],
        max_new_tokens: int,
        stop_sequences: Sequence[Sequence[int]] = (),
        tenant_id: Optional[str] = None,
    ) -> Tuple[np.ndarray, np.ndarray, int]:
        """Continuous-batched drop-in for the one-shot generate path.

        Returns ``(sequences [B, P+N], response_mask [B, N], P)`` with P the
        shared prompt bucket: prompts left-padded, responses padded with
        ``pad_token_id`` after finish, mask 1 on every generated token up to
        and including eos (``ops/generation.generate`` semantics — eos/stop
        trimming stays the consumer's job, exactly as ``decode`` expects).

        With ``tenant_id`` set, a batch member that ends shed or expired
        raises the matching typed error (tagged with the tenant) instead of
        silently returning a truncated row — a tenant-attributed rollout must
        be whole or loudly not. The default-tenant path keeps returning
        whatever outcome the engine produced, unchanged."""
        engine = self.engine
        N = int(max_new_tokens)
        P = pad_to_bucket(max((len(p) for p in prompts), default=1), LENGTH_BUCKETS)
        with self._step_lock:
            uids = [
                engine.submit(
                    np.asarray(p).tolist(), N, stop_sequences=stop_sequences,
                    tenant_id=tenant_id,
                )
                for p in prompts
            ]
            done = engine.run(uids)
        B = len(prompts)
        seqs = np.full((B, P + N), engine.pad_token_id, np.int32)
        mask = np.zeros((B, N), np.int32)
        t_store = engine.scheduler.clock() if flight.enabled else 0.0
        for i, (uid, p) in enumerate(zip(uids, prompts)):
            req = done[uid]
            engine.scheduler.pop_request(uid)
            # the consumer collecting the result closes the flight's
            # store_wait tail (stream_batch leaves this to the trainer's
            # dispatch, which stores per-sample after reward resolution)
            if flight.enabled:
                flight.record(uid, "store", t=t_store)
            if tenant_id is not None:
                if req.finish_reason == FINISH_SHED:
                    raise RequestShedError(
                        f"batch member uid={uid} was shed",
                        tenant_id=req.tenant_id, slo_class=req.slo_class,
                        replica_id=self._replica_of(uid), uid=uid,
                    )
                if req.finish_reason == FINISH_DEADLINE:
                    raise RequestExpiredError(
                        f"batch member uid={uid} expired "
                        f"(deadline_s={req.deadline_s})",
                        tenant_id=req.tenant_id, slo_class=req.slo_class,
                        replica_id=self._replica_of(uid), uid=uid,
                    )
            p = np.asarray(p, np.int32)
            gen = np.asarray(req.generated, np.int32)
            seqs[i, P - len(p):P] = p
            seqs[i, P:P + len(gen)] = gen
            mask[i, : len(gen)] = 1
        return seqs, mask, P

    def stream_batch(
        self,
        prompts: List[np.ndarray],
        max_new_tokens: int,
        on_finish: Callable[[int, Request], None],
        stop_sequences: Sequence[Sequence[int]] = (),
        on_step: Optional[Callable[[float, float], None]] = None,
    ) -> Tuple[np.ndarray, np.ndarray, int]:
        """:meth:`generate_batch` with per-sequence completion callbacks —
        the seam for stream-overlapped PPO (docs/serving.md).

        ``on_finish(i, req)`` fires exactly once per batch index ``i``, on the
        calling thread, as soon as the engine finishes that sequence — while
        the rest of the batch is still decoding. It runs under the client's
        step lock between engine rounds, so it must hand heavy work (reward
        RPCs, scoring) to another thread and return quickly; anything it
        blocks on stalls decode. Exactly-once holds across supervised engine
        restarts: a finished request adopted by a new engine generation is
        de-duplicated by uid before delivery.

        ``on_step(t0, t1)`` receives the ``time.perf_counter`` window of every
        engine round — the decode busy intervals the overlap ledger needs.

        Returns the same ``(sequences [B, P+N], response_mask [B, N], P)``
        contract as :meth:`generate_batch`.
        """
        engine = self.engine
        N = int(max_new_tokens)
        P = pad_to_bucket(max((len(p) for p in prompts), default=1), LENGTH_BUCKETS)
        done: Dict[int, Request] = {}

        with self._step_lock:
            uids = [
                engine.submit(np.asarray(p).tolist(), N, stop_sequences=stop_sequences)
                for p in prompts
            ]
            index_of = {uid: i for i, uid in enumerate(uids)}
            want = set(uids)

            def _deliver(finished: Dict[int, Request]) -> None:
                for uid, req in finished.items():
                    if uid in done:  # restart carry-over: already delivered
                        continue
                    done[uid] = req
                    idx = index_of.get(uid)
                    if idx is not None:
                        on_finish(idx, req)

            _deliver(dict(engine.scheduler.pop_finished()))
            while not (want <= set(done)):
                if not engine.scheduler.has_work:
                    raise EngineStoppedError(
                        f"engine drained with requests unaccounted: "
                        f"{want - set(done)}"
                    )
                t0 = time.perf_counter()
                # same contract as generate_batch/stream: the step lock IS the
                # serialization — one caller drives rounds of one continuous
                # batch, and on_finish fires between rounds under it (heavy
                # work is the callback's job to offload, see docstring)
                engine.step()  # graftcheck: noqa[CC005]
                t1 = time.perf_counter()
                if on_step is not None:
                    on_step(t0, t1)
                _deliver(dict(engine.scheduler.pop_finished()))
                engine.export_gauges()

        B = len(prompts)
        seqs = np.full((B, P + N), engine.pad_token_id, np.int32)
        mask = np.zeros((B, N), np.int32)
        for i, (uid, p) in enumerate(zip(uids, prompts)):
            req = done[uid]
            engine.scheduler.pop_request(uid)
            p = np.asarray(p, np.int32)
            gen = np.asarray(req.generated, np.int32)
            seqs[i, P - len(p):P] = p
            seqs[i, P:P + len(gen)] = gen
            mask[i, : len(gen)] = 1
        return seqs, mask, P

    def summary(self) -> Dict[str, float]:
        return self.engine.summary()
