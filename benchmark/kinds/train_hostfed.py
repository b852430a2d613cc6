"""Traffic kind ``train-hostfed``: the same rounds, fed from the host through
the path every app runs — ``MinibatchSampler`` windows over a host partition,
``stack_windows`` into ``RoundFeed``'s recycled buffer, a sharded
``device_put`` from its producer thread (``pipelined=True``, the app's
default depth), ``trainer.round`` on what ``next_round`` hands over."""

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.kinds import train_resident


def seeded_bytes(seed, shape, threads=8):
    """``shape`` uint8 from the seed; 64-bit draws on a few threads, because a
    partition of gigabytes drawn byte by byte would be most of the set-up."""
    out = np.empty(shape, np.uint8)
    words = out.reshape(-1)[: out.size // 8 * 8].view(np.uint64)
    cuts = np.linspace(0, words.size, threads + 1).astype(np.int64)

    def fill(i):
        rng = np.random.default_rng([seed, i])
        part = words[cuts[i]:cuts[i + 1]]
        part[:] = rng.integers(0, 2**64, part.size, np.uint64)

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(threads)))
    out.reshape(-1)[words.size * 8:] = 0
    return out


class Cell(train_resident.Cell):
    feed = None  # until make_data: close() runs whatever went wrong before

    def make_data(self):
        from sparknet_tpu.data import MinibatchSampler, RoundFeed, stack_windows
        from sparknet_tpu.parallel import shard_leading_global

        n, b, s = self.traffic["partition_minibatches"], self.batch, self.stored
        t0 = time.perf_counter()
        self.samplers = []
        for w in range(self.workers):
            rng = np.random.default_rng([self.seed, w, 1])
            labels = rng.integers(0, self.config["classes"], (n, b))
            self.samplers.append(MinibatchSampler(
                {
                    "data": seeded_bytes(self.seed + w, (n, b, 3, s, s)),
                    "label": labels.astype(np.float32),
                },
                num_sampled_batches=self.tau,
                seed=self.seed + w,
            ))
        self.log(f"host partition of {n} minibatches a worker in "
                 f"{time.perf_counter() - t0:.2f} s")
        self.feed = RoundFeed(
            lambda r, out: stack_windows(
                [smp.next_window() for smp in self.samplers], out
            ),
            place=lambda host: shard_leading_global(host, self.mesh),
            pipelined=True,
        )

    def next_round(self, r):
        return self.feed.next_round(r)

    def sample_frames(self, n):
        batches = self.samplers[0].batches
        return batches["data"][0, :n], batches["label"][0, :n]

    def close(self):
        if self.feed is not None:
            self.feed.stop()
