"""Batched, prefetching data loader feeding the device mesh.

Replaces the reference's ``torch.utils.data.DataLoader(num_workers=2)``
(``single.py:286``) with a threaded prefetch pipeline tuned for the TPU feed
pattern: batches are collated host-side into pinned numpy uint8 arrays (HWC),
prefetched ``prefetch_depth`` batches ahead so host IO overlaps device
compute, and transferred as uint8 — the /255 float conversion runs on-device
inside the jitted step, where XLA fuses it into the first convolution.

If the native C++ loader core (``ddl_tpu/native``) is built, sample decoding
and collation are delegated to it; otherwise a pure-Python thread pool is
used.  ``shard_batch`` places the host batch onto the mesh: dimension 0 is
sharded over the ``data`` axis and replicated over ``pipe`` — the same data
placement the reference assembles manually with ``DistributedSampler`` +
per-rank ``.to(device)`` (``ddp.py:180-183``).
"""

from __future__ import annotations

import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Tuple

import numpy as np

from ddl_tpu.data.sampler import ShardedEpochSampler
from ddl_tpu.utils import faultinject
from ddl_tpu.utils.backoff import Backoff, retry_with_backoff

__all__ = ["DataLoader", "shard_batch"]


class DataLoader:
    def __init__(
        self,
        dataset,
        batch_size: int,
        sampler: ShardedEpochSampler | None = None,
        shuffle: bool = True,
        drop_last: bool = True,
        num_workers: int = 2,
        prefetch_depth: int = 2,
        seed: int = 0,
        pad_last_batch: bool = False,
        io_retries: int = 2,
        on_retry=None,
        on_collate=None,
    ) -> None:
        self.dataset = dataset
        self.batch_size = batch_size
        self.sampler = sampler or ShardedEpochSampler(
            len(dataset), shuffle=shuffle, drop_last=drop_last, seed=seed
        )
        self.num_workers = max(0, num_workers)
        self.prefetch_depth = max(1, prefetch_depth)
        self.drop_last = drop_last
        # pad the final partial batch with -1 sentinels up to batch_size, so
        # every batch has the same static shape (one compiled SPMD eval fn)
        # and the consumer masks rows with label -1 (deterministic
        # full-coverage eval, reference single.py:199-258)
        self.pad_last_batch = pad_last_batch
        # Transient-I/O resilience: a flaky NAS read (OSError) is retried
        # with bounded backoff instead of killing the epoch; retries are
        # counted here and surfaced to the caller (trainers emit them as
        # ``io_retry`` obs events).  io_retries=0 restores fail-fast.
        self.io_retries = max(0, io_retries)
        self.on_retry = on_retry
        self.retry_count = 0
        # observability hook: ``on_collate(batch index in the epoch)``
        # returns a context manager the producer thread builds the batch
        # under (the trainers' ``collate`` span).  No hook, no cost.
        self.on_collate = on_collate
        # one policy object for the loader's lifetime — _fetch runs once
        # per sample in the hot path, and Backoff construction seeds an
        # RNG from OS entropy
        self._backoff = Backoff(base=0.05, factor=4.0, max_delay=2.0)

    def _note_retry(self, exc: BaseException, attempt: int) -> None:
        self.retry_count += 1
        if self.on_retry is not None:
            self.on_retry(exc, attempt)

    def _retry_io(self, fn):
        return retry_with_backoff(
            fn,
            retries=self.io_retries,
            exceptions=(OSError,),
            backoff=self._backoff,
            on_retry=self._note_retry,
        )

    def _fetch(self, idx) -> Tuple[np.ndarray, int]:
        def attempt():
            faultinject.io_check("batch")
            return self.dataset[int(idx)]

        return self._retry_io(attempt)

    def set_epoch(self, epoch: int) -> None:
        self.sampler.set_epoch(epoch)

    def set_start_batch(self, n: int) -> None:
        """Skip the first ``n`` batches of the NEXT iteration (one-shot;
        later epochs start at 0).  The exact-resume path: the sampler's
        permutation is deterministic in (seed, epoch), so dropping the
        first ``n`` index-batches replays precisely the batches a
        preempted epoch had not yet consumed — no sample is loaded and
        discarded, the skip happens on indices."""
        self._start_batch = max(0, int(n))

    def __len__(self) -> int:
        n = len(self.sampler)
        return n // self.batch_size if self.drop_last else -(-n // self.batch_size)

    def _collate(self, idxs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        idxs = np.asarray(idxs)
        n_pad = int((idxs < 0).sum())
        if n_pad:
            # sentinel (-1) indices: zero image, label -1 (mask-out rows)
            valid = idxs[idxs >= 0]
            if len(valid):
                images, labels = self._collate(valid)
            else:
                img0 = np.asarray(self.dataset[0][0])
                images = np.zeros((0, *img0.shape), img0.dtype)
                labels = np.zeros((0,), np.int32)
            images = np.concatenate(
                [images, np.zeros((n_pad, *images.shape[1:]), images.dtype)]
            )
            labels = np.concatenate([labels, np.full((n_pad,), -1, np.int32)])
            return images, labels
        images = self._collate_native(idxs)
        if images is None:
            if self.num_workers > 0:
                with ThreadPoolExecutor(self.num_workers) as pool:
                    samples = list(pool.map(self._fetch, idxs))
            else:
                samples = [self._fetch(i) for i in idxs]
            images = np.stack([s[0] for s in samples])
        labels = np.asarray(
            [self.dataset.labels[i] for i in idxs]
            if hasattr(self.dataset, "labels")
            else [self.dataset[i][1] for i in idxs],
            dtype=np.int32,
        )
        return images, labels

    def _collate_native(self, idxs: np.ndarray) -> np.ndarray | None:
        """Whole-batch decode through the C++ core (no per-sample Python),
        when the dataset is file-backed and the native lib is built."""
        if not hasattr(self.dataset, "image_path"):
            return None
        from ddl_tpu import native

        if not native.native_available():
            return None
        paths = [self.dataset.image_path(int(i)) for i in idxs]
        if not hasattr(self, "_hw"):
            hw = native.image_size(paths[0])
            if hw is None:
                return None
            self._hw = hw
        h, w = self._hw
        # the native decoder reads the same NAS files — same retry policy
        return self._retry_io(lambda: native.load_batch(paths, h, w))

    def _batches(self) -> Iterator[Tuple[int, np.ndarray]]:
        """(batch index in the epoch, its sample indices)."""
        idxs = np.asarray(list(self.sampler.indices()))
        n_full = len(idxs) // self.batch_size
        skip = getattr(self, "_start_batch", 0)
        self._start_batch = 0
        for b in range(skip, n_full):
            yield b, idxs[b * self.batch_size : (b + 1) * self.batch_size]
        if not self.drop_last and n_full * self.batch_size < len(idxs):
            tail = idxs[n_full * self.batch_size :]
            if self.pad_last_batch:
                tail = np.concatenate(
                    [tail, np.full(self.batch_size - len(tail), -1, tail.dtype)]
                )
            yield n_full, tail

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """Yield collated (uint8 images, int32 labels), prefetching ahead."""
        q: queue.Queue = queue.Queue(maxsize=self.prefetch_depth)
        sentinel = object()
        # a producer-thread failure must reach the consumer as the
        # original exception, not as a silently truncated epoch (which
        # would train on a shorter epoch and report nothing)
        error: list[BaseException] = []

        on_collate = self.on_collate

        def producer():
            try:
                for b, batch_idxs in self._batches():
                    if on_collate is None:
                        batch = self._collate(batch_idxs)
                    else:
                        with on_collate(b):
                            batch = self._collate(batch_idxs)
                    q.put(batch)
            except BaseException as e:
                error.append(e)
            finally:
                q.put(sentinel)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is sentinel:
                break
            yield item
        t.join()
        if error:
            raise error[0]


def shard_batch(mesh, images: np.ndarray, labels: np.ndarray):
    """Place a host batch onto the mesh, sharded over the ``data`` axis.

    Single-process: a ``device_put`` with ``NamedSharding(P('data'))``.
    Multi-host: each process holds its own shard (the sampler already split
    by process), assembled into one global jax.Array via
    ``make_array_from_process_local_data`` — the SPMD equivalent of the
    reference's per-rank loader + ``.to(device)``.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec_img = P("data", *([None] * (images.ndim - 1)))
    spec_lab = P("data")
    s_img = NamedSharding(mesh, spec_img)
    s_lab = NamedSharding(mesh, spec_lab)
    if jax.process_count() > 1:
        gi = jax.make_array_from_process_local_data(s_img, images)
        gl = jax.make_array_from_process_local_data(s_lab, labels)
        return gi, gl
    return jax.device_put(images, s_img), jax.device_put(labels, s_lab)
