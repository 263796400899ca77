"""Multiprocess DataLoader workers (reference io/reader.py:262
_DataLoaderIterMultiProcess): real worker processes, ordered batches,
get_worker_info, worker_init_fn, error propagation, graceful shutdown,
and throughput vs the thread pipeline."""
import time

import numpy as np
import pytest

from paddle_tpu import io


class _SquareDataset(io.Dataset):
    def __init__(self, n=32):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.full((3,), float(i), np.float32), np.int64(i)


def test_map_style_workers_preserve_order():
    ds = _SquareDataset(20)
    dl = io.DataLoader(ds, batch_size=4, num_workers=2, shuffle=False)
    xs, ys = [], []
    for x, y in dl:
        xs.append(x.numpy())
        ys.append(y.numpy())
    assert len(xs) == 5
    flat = np.concatenate(ys)
    np.testing.assert_array_equal(flat, np.arange(20))
    np.testing.assert_allclose(xs[2][0], np.full((3,), 8.0))


def test_results_match_single_process():
    ds = _SquareDataset(17)
    single = [y.numpy() for _, y in io.DataLoader(ds, batch_size=4,
                                                  num_workers=0)]
    multi = [y.numpy() for _, y in io.DataLoader(ds, batch_size=4,
                                                 num_workers=3)]
    assert len(single) == len(multi)
    for a, b in zip(single, multi):
        np.testing.assert_array_equal(a, b)


class _ShardedIterable(io.IterableDataset):
    def __init__(self, n=24):
        self.n = n

    def __iter__(self):
        info = io.get_worker_info()
        wid = info.id if info else 0
        nw = info.num_workers if info else 1
        for i in range(wid, self.n, nw):  # worker-sharded stream
            yield np.int64(i)


def test_iterable_workers_shard_via_worker_info():
    dl = io.DataLoader(_ShardedIterable(24), batch_size=4, num_workers=2)
    got = sorted(int(v) for b in dl for v in b.numpy())
    assert got == list(range(24))


def test_worker_init_fn_and_error_propagation(tmp_path):
    calls = tmp_path / "init_calls"
    calls.mkdir()

    def init(worker_id):
        (calls / f"w{worker_id}").write_text("up")

    ds = _SquareDataset(8)
    list(io.DataLoader(ds, batch_size=4, num_workers=2,
                       worker_init_fn=init))
    assert (calls / "w0").exists() and (calls / "w1").exists()

    class Bad(io.Dataset):
        def __len__(self):
            return 4

        def __getitem__(self, i):
            if i == 2:
                raise ValueError("boom at 2")
            return np.zeros(2, np.float32)

    with pytest.raises(RuntimeError, match="boom at 2"):
        list(io.DataLoader(Bad(), batch_size=2, num_workers=2))


class _SlowDataset(io.Dataset):
    """Every fetch sleeps (an I/O-bound item) and leaves a record of who
    made it and when: ``log/<item>`` holds "worker start end" on the
    machine-wide monotonic clock (worker -1: the loader's own process)."""

    def __init__(self, log):
        self.log = log

    def __len__(self):
        return 16

    def __getitem__(self, i):
        info = io.get_worker_info()
        start = time.monotonic()
        time.sleep(0.03)
        (self.log / str(i)).write_text(
            f"{info.id if info else -1} {start} {time.monotonic()}")
        return np.full((2,), float(i), np.float32)


def _fetches(log):
    """[(worker, start, end)] of every recorded fetch, by start."""
    out = []
    for f in log.iterdir():
        worker, start, end = f.read_text().split()
        out.append((int(worker), float(start), float(end)))
    return sorted(out, key=lambda r: r[1])


def _in_flight_together(fetches):
    """Pairs of fetches of DIFFERENT workers whose intervals overlap."""
    return [(a, b) for n, a in enumerate(fetches) for b in fetches[n + 1:]
            if a[0] != b[0] and b[1] < a[2]]


def test_multiprocess_beats_serial_on_io_bound_fetch(tmp_path):
    """What four workers buy on an I/O-bound fetch is that fetches of
    different workers are in flight at once.  That is asserted from the
    intervals the dataset records, which a loaded machine stretches but
    cannot pull apart; the wall times of the two loaders, which it can
    reorder, are not compared."""
    logs = {}
    for workers in (0, 4):
        logs[workers] = tmp_path / f"workers{workers}"
        logs[workers].mkdir()
        batches = list(io.DataLoader(_SlowDataset(logs[workers]),
                                     batch_size=4, num_workers=workers))
        assert len(batches) == 4
    serial, multi = _fetches(logs[0]), _fetches(logs[4])
    assert len(serial) == len(multi) == 16
    assert {w for w, _, _ in serial} == {-1}
    assert all(b[1] >= a[2] for a, b in zip(serial, serial[1:]))
    assert {w for w, _, _ in multi} == {0, 1, 2, 3}
    assert _in_flight_together(multi), multi


def test_graceful_shutdown_on_early_break():
    ds = _SquareDataset(32)
    dl = io.DataLoader(ds, batch_size=4, num_workers=2)
    it = iter(dl)
    next(it)
    it.close()  # must not hang or leak workers
