"""Process-based DataLoader workers.

Parity: reference fluid/dataloader/dataloader_iter.py:469
_DataLoaderIterMultiProcess — forked workers, ordered results, error
and dead-worker propagation. Python-heavy per-sample work (holds the
GIL) is what the thread pool cannot spread: the last test shows that
such work runs in several forked processes and comes back in order.
"""
import os
import time

import numpy as np
import pytest

from paddle_tpu.io import DataLoader, Dataset, get_worker_info


class _Square(Dataset):
    def __init__(self, n=64):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.asarray([i, i * i], np.float32)


class _PythonHeavy(Dataset):
    """Per-sample pure-Python loop: holds the GIL, the worst case for
    thread workers and the reason the reference forks processes."""

    def __init__(self, n=48, iters=60000):
        self.n = n
        self.iters = iters

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        acc = 0
        for k in range(self.iters):
            acc += (i * k) % 7
        # the last column names the process that did the work
        return np.asarray([i, acc, os.getpid()], np.float64)


class _FaultyAt(Dataset):
    def __init__(self, bad=13, n=32):
        self.bad, self.n = bad, n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i == self.bad:
            raise ValueError(f"poison sample {i}")
        return np.asarray([i], np.float32)


class _KillSelf(Dataset):
    """Simulates an OOM-killed / segfaulted worker."""

    def __init__(self, n=32):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i == 5:
            import os
            os._exit(137)
        return np.asarray([i], np.float32)


def _collect(loader):
    return [np.asarray(b.numpy()) for b in loader]


def test_process_workers_match_sync_order():
    ds = _Square(64)
    sync = _collect(DataLoader(ds, batch_size=8))
    proc = _collect(DataLoader(ds, batch_size=8, num_workers=3,
                               use_process=True))
    assert len(sync) == len(proc) == 8
    for a, b in zip(sync, proc):
        np.testing.assert_array_equal(a, b)


def test_process_workers_multiple_epochs():
    dl = DataLoader(_Square(32), batch_size=8, num_workers=2,
                    use_process=True)
    for _ in range(3):
        assert len(_collect(dl)) == 4


def test_worker_exception_propagates_with_trace():
    dl = DataLoader(_FaultyAt(13), batch_size=8, num_workers=2,
                    use_process=True)
    with pytest.raises(RuntimeError, match="poison sample 13"):
        _collect(dl)


def test_dead_worker_raises_instead_of_hanging():
    dl = DataLoader(_KillSelf(), batch_size=4, num_workers=2,
                    use_process=True)
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="exited unexpectedly"):
        _collect(dl)
    assert time.monotonic() - t0 < 30.0


def test_worker_info_inside_process():
    class _Probe(Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            info = get_worker_info()
            assert info is not None and info.num_workers == 2
            return np.asarray([info.id], np.float32)

    seen = np.concatenate(_collect(
        DataLoader(_Probe(), batch_size=2, num_workers=2,
                   use_process=True))).ravel()
    assert set(seen) <= {0.0, 1.0}


def test_early_break_releases_workers():
    dl = DataLoader(_Square(64), batch_size=4, num_workers=2,
                    use_process=True)
    for i, _ in enumerate(dl):
        if i == 2:
            break
    # a second full pass still works (no leaked/poisoned state)
    assert len(_collect(dl)) == 16


def test_python_heavy_transforms_run_in_several_worker_processes():
    # no wall-clock ratio: a correctness gate shares its cores with five
    # other pytest workers.  What process mode owes is the same batches
    # in the same order, computed outside this process by more than one
    # worker.
    ds = _PythonHeavy()
    a = _collect(DataLoader(ds, batch_size=8))
    b = _collect(DataLoader(ds, batch_size=8, num_workers=4,
                            use_process=True))
    assert len(a) == len(b) == 6
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x[:, :2], y[:, :2])
    here = float(os.getpid())
    assert {p for x in a for p in x[:, 2]} == {here}
    pids = {p for y in b for p in y[:, 2]}
    assert here not in pids and len(pids) >= 2, pids
