"""Federated batching: per-client iterators -> stacked (N, b, ...) batches.

The production train step consumes one batch per client per local update,
stacked on the leading client axis (matching the stacked-parameter layout in
``core.hierfavg``). The pipeline:

  1. holds each client's index set (from ``data.partition``),
  2. reshuffles each client's samples every local epoch (client-seeded,
     reproducible, restart-safe: state = (epoch, cursor) per client),
  3. emits pytree batches with leaves shaped (N, b, ...) — or
     (kappa1, N, b, ...) for the scanned ``hier_round`` driver.

Also provides ``global_batch_iterator`` for the plain (non-federated)
LM training path used by the serving/dry-run drivers.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

PyTree = Any


@dataclasses.dataclass
class ClientCursor:
    epoch: int = 0
    pos: int = 0


class FederatedBatcher:
    """Stateful, restart-safe federated batcher.

    arrays: dict of data arrays (first axis = sample). batch_fn maps a dict
    of per-sample slices to the model's batch pytree (default: identity).
    """

    def __init__(
        self,
        arrays: Dict[str, np.ndarray],
        client_indices: Sequence[np.ndarray],
        batch_size: int,
        *,
        seed: int = 0,
        batch_fn: Optional[Callable[[Dict[str, np.ndarray]], PyTree]] = None,
    ):
        self.arrays = arrays
        self.client_indices = [np.asarray(ix) for ix in client_indices]
        self.batch_size = batch_size
        self.seed = seed
        self.batch_fn = batch_fn or (lambda d: d)
        self.cursors = [ClientCursor() for _ in client_indices]
        self._orders: List[np.ndarray] = [self._order(i) for i in range(len(client_indices))]

    @property
    def num_clients(self) -> int:
        return len(self.client_indices)

    @property
    def data_sizes(self) -> np.ndarray:
        return np.array([ix.shape[0] for ix in self.client_indices], np.float64)

    def _order(self, client: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, client, self.cursors[client].epoch))
        return rng.permutation(self.client_indices[client])

    def _next_for(self, client: int) -> np.ndarray:
        cur = self.cursors[client]
        order = self._orders[client]
        b = self.batch_size
        if cur.pos + b > order.shape[0]:
            cur.epoch += 1
            cur.pos = 0
            self._orders[client] = order = self._order(client)
        take = order[cur.pos : cur.pos + b]
        cur.pos += b
        return take

    def next_batch(self) -> PyTree:
        """One stacked batch: leaves (N, b, ...)."""
        rows = [self._next_for(i) for i in range(self.num_clients)]
        idx = np.stack(rows)  # (N, b)
        return self.batch_fn({k: v[idx] for k, v in self.arrays.items()})

    def next_batches(self, count: int) -> PyTree:
        """`count` stacked batches with a leading scan axis: (count, N, b, ...)."""
        outs = [self.next_batch() for _ in range(count)]
        import jax

        return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *outs)

    # -- sampled-participation view ------------------------------------------
    def next_batch_for(self, ids: Sequence[int]) -> PyTree:
        """One cohort batch: leaves (C, b, ...); advances only the sampled
        clients' cursors. With ids == range(N) this is ``next_batch`` exactly
        (same per-client draw order), which is what full-participation
        parity rests on."""
        rows = [self._next_for(int(i)) for i in ids]
        idx = np.stack(rows)  # (C, b)
        return self.batch_fn({k: v[idx] for k, v in self.arrays.items()})

    def next_batches_for(self, ids: Sequence[int], count: int) -> PyTree:
        """`count` cohort batches with a leading scan axis: (count, C, b, ...)."""
        outs = [self.next_batch_for(ids) for _ in range(count)]
        import jax

        return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *outs)

    # -- restart safety ------------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        return {
            "seed": self.seed,
            "cursors": [(c.epoch, c.pos) for c in self.cursors],
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.seed = state["seed"]
        for c, (e, p) in zip(self.cursors, state["cursors"]):
            c.epoch, c.pos = e, p
        self._orders = [self._order(i) for i in range(self.num_clients)]


class VirtualClientBatcher:
    """A population of N *virtual* clients over a shared sample pool.

    At population scale (ROADMAP's "millions of users") materializing N
    per-client index sets up front is O(N) host memory and startup time.
    Here a client's shard is a pure function of ``(seed, client_id)`` —
    ``samples_per_client`` bootstrap draws from the pool, realized lazily
    only when that client is actually sampled into a cohort. Per-epoch
    shuffle order is likewise derived from ``(seed, client_id, epoch)``.
    Cursor state is a dict holding only the clients that ever participated,
    so batcher memory is ∝ cumulative unique participants, not N.

    Interface-compatible with the cohort slice of ``FederatedBatcher``
    (``next_batch_for`` / ``next_batches_for`` / ``state_dict``); the
    full-population ``next_batch`` works too but is intended only for small
    N (tests).
    """

    _SHARD_NS = 0x5A4D  # namespaces the shard draw away from the order draw

    def __init__(
        self,
        arrays: Dict[str, np.ndarray],
        *,
        num_clients: int,
        samples_per_client: int,
        batch_size: int,
        seed: int = 0,
        batch_fn: Optional[Callable[[Dict[str, np.ndarray]], PyTree]] = None,
    ):
        self.arrays = arrays
        self.num_clients = int(num_clients)
        self.samples_per_client = int(samples_per_client)
        self.batch_size = int(batch_size)
        self.seed = int(seed)
        self.batch_fn = batch_fn or (lambda d: d)
        self.num_samples = int(next(iter(arrays.values())).shape[0])
        if self.samples_per_client < self.batch_size:
            raise ValueError(
                f"samples_per_client {self.samples_per_client} < batch_size {self.batch_size}"
            )
        self.cursors: Dict[int, ClientCursor] = {}

    @property
    def data_sizes(self) -> np.ndarray:
        return np.full(self.num_clients, self.samples_per_client, np.float64)

    def _shard(self, client: int) -> np.ndarray:
        """(samples_per_client,) pool indices — the client's virtual dataset."""
        rng = np.random.default_rng((self.seed, self._SHARD_NS, client))
        return rng.integers(0, self.num_samples, self.samples_per_client)

    def _order(self, client: int, epoch: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed, client, epoch))
        return rng.permutation(self.samples_per_client)

    def _take_rows(self, client: int, nbatches: int) -> np.ndarray:
        """(nbatches, b) pool indices; advances the client's cursor. Epoch
        semantics mirror ``FederatedBatcher._next_for`` (partial trailing
        batches are never emitted; the epoch reshuffles instead)."""
        cur = self.cursors.setdefault(client, ClientCursor())
        shard = self._shard(client)
        b = self.batch_size
        order = None
        out = np.empty((nbatches, b), np.int64)
        for j in range(nbatches):
            if cur.pos + b > self.samples_per_client:
                cur.epoch += 1
                cur.pos = 0
                order = None
            if order is None:
                order = self._order(client, cur.epoch)
            out[j] = shard[order[cur.pos : cur.pos + b]]
            cur.pos += b
        return out

    def next_batch_for(self, ids: Sequence[int]) -> PyTree:
        """One cohort batch: leaves (C, b, ...)."""
        rows = np.stack([self._take_rows(int(c), 1)[0] for c in ids])  # (C, b)
        return self.batch_fn({k: v[rows] for k, v in self.arrays.items()})

    def next_batches_for(self, ids: Sequence[int], count: int) -> PyTree:
        """`count` cohort batches with a leading scan axis: (count, C, b, ...)."""
        rows = np.stack([self._take_rows(int(c), count) for c in ids], axis=1)
        return self.batch_fn({k: v[rows] for k, v in self.arrays.items()})

    def next_batch(self) -> PyTree:
        """Full-population batch (small-N testing only at scale N)."""
        return self.next_batch_for(range(self.num_clients))

    def next_batches(self, count: int) -> PyTree:
        outs = [self.next_batch() for _ in range(count)]
        import jax

        return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *outs)

    # -- restart safety ------------------------------------------------------
    def state_dict(self) -> Dict[str, Any]:
        # string keys: this dict rides checkpoint metadata through JSON,
        # which stringifies int keys — normalize here so save/load is stable
        return {
            "seed": self.seed,
            "cursors": {str(c): (cur.epoch, cur.pos) for c, cur in self.cursors.items()},
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.seed = int(state["seed"])
        self.cursors = {
            int(c): ClientCursor(epoch=int(e), pos=int(p))
            for c, (e, p) in state["cursors"].items()
        }


class SuperBatchPrefetcher:
    """Double-buffered host→device prefetch of super-round batch blocks.

    The superround engine (``fed.engine``) consumes one
    (rounds_per_block, steps_per_round, N, b, ...) block per cloud-interval
    dispatch. Assembling that block is host work (numpy gathers) and
    uploading it is a host→device copy — both off the critical path once
    the device is busy with interval r: a background worker builds and
    ``jax.device_put``s interval r+1's block while interval r computes, so
    the dispatch loop never waits on batch assembly (double buffering; the
    bounded queue holds at most ``prefetch`` ready blocks).

    Restart safety: each block is paired with the batcher's ``state_dict``
    snapshot taken right after producing it — i.e. the cursor state a
    checkpoint at that block's cloud boundary must record. The live batcher
    runs ahead of the computation, so checkpoints must use the snapshot,
    never ``batcher.state_dict()`` directly.

    ``num_blocks`` bounds total production so the batcher is left positioned
    exactly after the engine's rounds (a per-round fallback can continue
    from it). ``use_thread=False`` degrades to synchronous production (no
    overlap — deterministic single-threaded mode for tests/debugging).
    The worker is the sole batcher consumer while the prefetcher is active.

    Mesh execution: ``device`` may be a ``jax.sharding.Sharding`` (e.g. the
    engine's ``NamedSharding`` over the ``"clients"`` axis), in which case
    ``device_put`` uploads each device's block slice directly instead of a
    single-device copy; ``transform`` is an optional host-side (numpy) hook
    applied to the assembled block before upload — the engine uses it to
    permute + pad the client axis into shard placement order.

    Tracing: the worker's host work is named ``data.block_gather`` and
    ``data.block_upload`` (the latter with the block's ``bytes``) in profiler
    traces; both carry ``interval``, the cloud interval that consumes the
    block (``first_interval`` plus the blocks made before it), as the
    engine's ``fed.*`` spans do.
    """

    _SENTINEL_OK = "ok"
    _SENTINEL_ERR = "err"

    def __init__(
        self,
        batcher: FederatedBatcher,
        *,
        rounds_per_block: int,
        steps_per_round: int,
        num_blocks: Optional[int] = None,
        device=None,
        prefetch: int = 1,
        use_thread: bool = True,
        transform: Optional[Callable[[PyTree], PyTree]] = None,
        first_interval: int = 0,
    ):
        self.batcher = batcher
        self.rounds_per_block = int(rounds_per_block)
        self.steps_per_round = int(steps_per_round)
        self.num_blocks = num_blocks
        self.device = device
        self.transform = transform
        self.first_interval = int(first_interval)
        self._block_bytes: Optional[int] = None  # host bytes of one block, read once
        self._produced = 0
        self._consumed = 0
        self._use_thread = use_thread
        if use_thread:
            self._queue: queue.Queue = queue.Queue(maxsize=max(1, int(prefetch)))
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._worker, name="super-batch-prefetch", daemon=True
            )
            self._thread.start()

    # -- block production ----------------------------------------------------
    def _upload_bytes(self, tree: PyTree) -> int:
        if self._block_bytes is None:  # every block has the same shapes
            import jax

            self._block_bytes = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))
        return self._block_bytes

    def _make_block(self) -> Tuple[PyTree, Dict[str, Any]]:
        import jax
        from jax.profiler import TraceAnnotation

        interval = self.first_interval + self._produced
        with TraceAnnotation("data.block_gather", interval=interval):
            flat = self.batcher.next_batches(self.rounds_per_block * self.steps_per_round)
            block = jax.tree_util.tree_map(
                lambda x: np.reshape(
                    x, (self.rounds_per_block, self.steps_per_round) + x.shape[1:]
                ),
                flat,
            )
            if self.transform is not None:
                block = self.transform(block)
        with TraceAnnotation("data.block_upload", interval=interval, bytes=self._upload_bytes(block)):
            block = jax.device_put(block, self.device)  # async upload
        snapshot = self.batcher.state_dict()
        return block, snapshot

    def _worker(self) -> None:
        try:
            while not self._stop.is_set() and (
                self.num_blocks is None or self._produced < self.num_blocks
            ):
                item = (self._SENTINEL_OK,) + self._make_block()
                self._produced += 1
                while not self._stop.is_set():
                    try:
                        self._queue.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
        except Exception as e:  # surface worker failures at the next get()
            self._queue.put((self._SENTINEL_ERR, e, None))

    # -- consumption ---------------------------------------------------------
    @property
    def ready(self) -> bool:
        """Whether the next block is already queued, so ``get()`` will not
        wait (always False without the worker thread)."""
        return self._use_thread and not self._queue.empty()

    def get(self) -> Tuple[PyTree, Dict[str, Any]]:
        """Next (device_block, batcher_state_snapshot). Blocks until ready."""
        if self.num_blocks is not None and self._consumed >= self.num_blocks:
            raise RuntimeError(
                f"prefetcher exhausted: all {self.num_blocks} blocks consumed"
            )
        if self._use_thread:
            kind, block, snapshot = self._queue.get()
            if kind == self._SENTINEL_ERR:
                raise RuntimeError("super-batch prefetch worker failed") from block
        else:
            block, snapshot = self._make_block()
            self._produced += 1
        self._consumed += 1
        return block, snapshot

    def stop(self) -> None:
        """Stop the worker (idempotent). Call when abandoning blocks early."""
        if not self._use_thread:
            return
        self._stop.set()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)

    def __enter__(self) -> "SuperBatchPrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


class CohortPrefetcher(SuperBatchPrefetcher):
    """``SuperBatchPrefetcher`` for sampled participation.

    The worker additionally draws the next cloud interval's cohort from a
    ``fed.participation`` sampler and assembles + uploads everything that is
    a pure function of the cohort ids — the (κ₂, κ₁, C, b, ...) batch block
    and the traced ``{"segments": (depth-1, C), "weights": (C,)}`` cohort
    pytree the cohort superround consumes — so sampling, batch gathers, and
    the host→device copies all overlap the previous interval's compute.
    The *client-state* rows are deliberately NOT prefetched: consecutive
    cohorts may overlap, and a row gathered before the previous interval's
    writeback would be stale; the engine swaps store rows synchronously
    (a C-row host gather — cheap next to the batch upload this class hides).

    Restart-exactness: each block's snapshot carries the *sampler* state
    alongside the batcher cursors, both captured right after producing the
    block. The live sampler runs ahead of the computation (prefetch), so a
    checkpoint that recorded the live state would replay *different* cohorts
    on resume — checkpoints must store the snapshot, mirroring the batcher
    contract above.

    ``get()`` returns ``((ids, cohort, block), snapshot)``: host-side int64
    ids for store gather/scatter, device-resident cohort arrays + block, and
    ``snapshot = {"batcher": ..., "sampler": ...}``.
    """

    def __init__(
        self,
        batcher,
        sampler,
        *,
        segments: np.ndarray,
        weights: np.ndarray,
        rounds_per_block: int,
        steps_per_round: int,
        num_blocks: Optional[int] = None,
        device=None,
        prefetch: int = 1,
        use_thread: bool = True,
        placement=None,
        weights_device=None,
        first_interval: int = 0,
    ):
        # fields first: the base __init__ starts the worker thread, which
        # calls our _make_block immediately
        self.sampler = sampler
        self._segments = np.ascontiguousarray(np.asarray(segments, np.int32))
        self._weights = np.asarray(weights, np.float32)
        # sharded-cohort mode: with a `placement` (cohort ShardPlacement) the
        # worker permutes the block's client axis into slot placement order,
        # pads, and uploads per-device slices — `device` is then the block's
        # NamedSharding and `weights_device` the (padded_C,) row sharding.
        # Segments are not uploaded: placement-stable packing makes every
        # segment table static in the sharded lowering.
        self._placement = placement
        self._weights_device = weights_device
        super().__init__(
            batcher,
            rounds_per_block=rounds_per_block,
            steps_per_round=steps_per_round,
            num_blocks=num_blocks,
            device=device,
            prefetch=prefetch,
            use_thread=use_thread,
            first_interval=first_interval,
        )

    def _make_block(self):
        import jax
        from jax.profiler import TraceAnnotation

        interval = self.first_interval + self._produced
        with TraceAnnotation("data.cohort_sample", interval=interval):
            ids = np.asarray(self.sampler.sample(), np.int64)
        with TraceAnnotation("data.block_gather", interval=interval):
            flat = self.batcher.next_batches_for(ids, self.rounds_per_block * self.steps_per_round)
            block = jax.tree_util.tree_map(
                lambda x: np.reshape(
                    x, (self.rounds_per_block, self.steps_per_round) + x.shape[1:]
                ),
                flat,
            )
            if self._placement is not None:
                # slot placement order: phantom slots replicate slot 0's batch
                # (their weight is zero), matching the sharded superround's pad
                gather = self._placement.gather_index()
                block = jax.tree_util.tree_map(lambda x: x[:, :, gather], block)
                cohort = {"weights": self._placement.pad_weights(self._weights[ids])}
            else:
                cohort = {
                    "segments": self._segments[:, ids],
                    "weights": self._weights[ids],
                }
        with TraceAnnotation("data.block_upload", interval=interval, bytes=self._upload_bytes((cohort, block))):
            if self._placement is not None:
                block = jax.device_put(block, self.device)  # async per-device upload
                cohort = jax.device_put(cohort, self._weights_device)
            else:
                cohort, block = jax.device_put((cohort, block), self.device)  # async upload
        snapshot = {
            "batcher": self.batcher.state_dict(),
            "sampler": self.sampler.state_dict(),
        }
        return (ids, cohort, block), snapshot


def global_batch_iterator(
    arrays: Dict[str, np.ndarray], batch_size: int, *, seed: int = 0
) -> Iterator[Dict[str, np.ndarray]]:
    """Simple epoch-shuffled global iterator (non-federated paths)."""
    n = next(iter(arrays.values())).shape[0]
    epoch = 0
    while True:
        rng = np.random.default_rng((seed, epoch))
        order = rng.permutation(n)
        for s in range(0, n - batch_size + 1, batch_size):
            take = order[s : s + batch_size]
            yield {k: v[take] for k, v in arrays.items()}
        epoch += 1
