"""paddle.distributed.spawn + ParallelMode + mp split + PS datasets.

Reference: python/paddle/distributed/spawn.py (mp.spawn worker pool),
parallel.py ParallelMode, collective.split (mp layer builder),
fleet InMemoryDataset/QueueDataset + table entry configs
(python/paddle/distributed/entry_attr.py, fleet/dataset/).
"""
from __future__ import annotations

import multiprocessing as mp
import os

__all__ = ["spawn", "ParallelMode", "split", "InMemoryDataset",
           "QueueDataset", "CountFilterEntry", "ShowClickEntry",
           "ProbabilityEntry"]


class ParallelMode:
    """Reference python/paddle/distributed/parallel.py:ParallelMode."""

    COLLECTIVE = 0
    PS = 1
    HETER_PS = 2


def _spawn_worker(func, rank, nprocs, args, env):
    for k, v in env.items():
        os.environ[k] = v
    os.environ["PADDLE_TRAINER_ID"] = str(rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(nprocs)
    os.environ["FLAGS_selected_devices"] = str(rank)
    func(*args)


def spawn(func, args=(), nprocs=1, join=True, daemon=False, **options):
    """paddle.distributed.spawn parity: run ``func`` in ``nprocs``
    processes with the launcher's env protocol (PADDLE_TRAINER_ID /
    PADDLE_TRAINERS_NUM). Returns the process list (a MultiprocessContext
    stand-in when join=False).

    One process per chip: every worker is a fresh interpreter that
    initialises its OWN JAX backend from the parent's environment plus
    ``env=`` (that is where a worker is told what it runs on —
    ``JAX_PLATFORMS``, a per-rank device mask). A parent that has
    already touched JAX on a TPU holds that chip, and a worker that
    needs it fails or hangs: keep the parent off JAX, as
    ``parallel/launch/main.py`` does, or hand each worker its own
    devices through ``env=``."""
    ctx = mp.get_context("spawn")
    base_env = {k: str(v) for k, v in options.get("env", {}).items()}
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_spawn_worker,
                        args=(func, rank, nprocs, tuple(args), base_env),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
        bad = [p.exitcode for p in procs if p.exitcode]
        if bad:
            raise RuntimeError(f"spawned workers failed: exit codes {bad}")
    return procs


def split(x, size, operation="linear", axis=0, num_partitions=1,
          gather_out=True, weight_attr=None, bias_attr=None, name=None):
    """paddle.distributed.split parity (reference collective.py split):
    build + apply a model-parallel layer over the 'mp' mesh axis.

    operation='linear': size=(in, out) columns split (axis=1) or rows
    (axis=0); operation='embedding': vocab-parallel embedding."""
    from ..nn import Linear
    from .mp_layers import (ColumnParallelLinear, RowParallelLinear,
                            VocabParallelEmbedding)
    if operation == "embedding":
        layer = VocabParallelEmbedding(size[0], size[1])
        return layer(x)
    if operation != "linear":
        raise ValueError(f"unsupported operation {operation!r}")
    if axis == 1:
        layer = ColumnParallelLinear(size[0], size[1],
                                     has_bias=bias_attr is not False,
                                     gather_output=gather_out)
    elif axis == 0:
        layer = RowParallelLinear(size[0], size[1],
                                  has_bias=bias_attr is not False,
                                  input_is_parallel=not gather_out)
    else:
        raise ValueError("axis must be 0 or 1")
    return layer(x)


# --------------------------------------------------- PS dataset surface


class _EntryAttr:
    def __init__(self):
        self._name = None

    def _to_attr(self):
        return self._name


class CountFilterEntry(_EntryAttr):
    """Admit a sparse feature only after `count_filter` occurrences
    (reference entry_attr.py:CountFilterEntry)."""

    def __init__(self, count_filter):
        super().__init__()
        if count_filter < 0:
            raise ValueError("count_filter must be >= 0")
        self.count_filter = int(count_filter)
        self._name = f"count_filter_entry:{count_filter}"


class ShowClickEntry(_EntryAttr):
    """Track show/click stats per feature (entry_attr.py:ShowClickEntry)."""

    def __init__(self, show_name, click_name):
        super().__init__()
        self.show_name = show_name
        self.click_name = click_name
        self._name = f"show_click_entry:{show_name}:{click_name}"


class ProbabilityEntry(_EntryAttr):
    """Admit with probability (entry_attr.py:ProbabilityEntry)."""

    def __init__(self, probability):
        super().__init__()
        if not 0 <= probability <= 1:
            raise ValueError("probability must be in [0, 1]")
        self.probability = float(probability)
        self._name = f"probability_entry:{probability}"


class _DatasetBase:
    """Minimal fleet dataset surface: var binding + batch/thread config +
    file list; samples parsed as whitespace-separated slots per line
    (the reference's data_feed protocol simplified to host numpy)."""

    def __init__(self):
        self._batch_size = 1
        self._thread_num = 1
        self._use_vars = []
        self._filelist = []
        self._pipe_command = None

    def init(self, batch_size=1, thread_num=1, use_var=None,
             pipe_command=None, input_type=0, fs_name="", fs_ugi="",
             **kwargs):
        self._batch_size = batch_size
        self._thread_num = thread_num
        self._use_vars = use_var or []
        self._pipe_command = pipe_command

    def set_filelist(self, filelist):
        self._filelist = list(filelist)

    def set_batch_size(self, batch_size):
        self._batch_size = batch_size

    def set_thread(self, thread_num):
        self._thread_num = thread_num

    def set_use_var(self, use_vars):
        self._use_vars = list(use_vars)

    def _read_lines(self):
        for path in self._filelist:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if line:
                        yield line

    def _parse(self, line):
        import numpy as np
        parts = line.split()
        return np.asarray([float(p) for p in parts], np.float32)

    def __iter__(self):
        import numpy as np
        buf = []
        for line in self._read_lines():
            buf.append(self._parse(line))
            if len(buf) == self._batch_size:
                yield np.stack(buf)
                buf = []
        if buf:
            yield np.stack(buf)


class QueueDataset(_DatasetBase):
    """Streaming dataset (reference QueueDataset): single pass over files."""


class InMemoryDataset(_DatasetBase):
    """Load-then-shuffle dataset (reference InMemoryDataset)."""

    def __init__(self):
        super().__init__()
        self._samples = None

    def load_into_memory(self):
        self._samples = [self._parse(line) for line in self._read_lines()]

    def local_shuffle(self):
        import numpy as np
        if self._samples is None:
            self.load_into_memory()
        idx = np.random.permutation(len(self._samples))
        self._samples = [self._samples[i] for i in idx]

    def global_shuffle(self, fleet=None, thread_num=12):
        self.local_shuffle()

    def release_memory(self):
        self._samples = None

    def get_memory_data_size(self, fleet=None):
        return len(self._samples or [])

    def __iter__(self):
        import numpy as np
        if self._samples is None:
            self.load_into_memory()
        for i in range(0, len(self._samples), self._batch_size):
            yield np.stack(self._samples[i:i + self._batch_size])
