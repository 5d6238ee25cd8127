"""Native shm ring + multiprocess DataLoader workers.

~ reference test_multiprocess_dataloader_static/dynamic.py + the
shared-memory transport of dataloader_iter.py:542: worker processes
stream batches through csrc/shm_ring.cc; order, exceptions, multi-epoch
and ragged tails all behave like the in-process loader.
"""
import time

import numpy as np
import pytest

from paddle_tpu.io import DataLoader, Dataset, TensorDataset
from paddle_tpu.utils import native

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native lib unavailable")


class _DS(Dataset):
    def __len__(self):
        return 37

    def __getitem__(self, i):
        return np.full((3,), i, np.float32), np.int64(i % 5)


class _Boom(Dataset):
    def __len__(self):
        return 8

    def __getitem__(self, i):
        if i == 5:
            raise ValueError("boom at 5")
        return np.zeros(2, np.float32)


@needs_native
class TestShmRing:
    def test_write_read_roundtrip(self):
        from paddle_tpu.io.shm_channel import ShmRing
        ring = ShmRing("/pt_test_ring_a", slot_size=128, n_slots=4,
                       create=True)
        reader = ShmRing("/pt_test_ring_a", create=False)
        ring.write(b"hello")
        ring.write(b"world")
        assert reader.read() == b"hello"
        assert reader.read() == b"world"
        assert reader.read(timeout_us=10_000) is None  # empty -> timeout
        reader.close()
        ring.close()

    def test_oversize_record_raises(self):
        from paddle_tpu.io.shm_channel import ShmRing
        ring = ShmRing("/pt_test_ring_b", slot_size=16, n_slots=2,
                       create=True)
        with pytest.raises(ValueError, match="slot_size"):
            ring.write(b"x" * 1000)
        ring.close()

    def test_wraparound_more_records_than_slots(self):
        from paddle_tpu.io.shm_channel import ShmRing
        ring = ShmRing("/pt_test_ring_c", slot_size=64, n_slots=2,
                       create=True)
        out = []
        # interleave so the 2-slot ring wraps many times
        for i in range(10):
            ring.write(f"rec{i}".encode())
            out.append(ring.read())
        assert out == [f"rec{i}".encode() for i in range(10)]
        ring.close()


@needs_native
class TestMultiprocessLoader:
    def test_order_preserved(self):
        dl = DataLoader(_DS(), batch_size=8, num_workers=2, shuffle=False)
        it = iter(dl)
        from paddle_tpu.io.shm_channel import MultiprocessDataLoaderIter
        assert isinstance(it, MultiprocessDataLoaderIter)
        flat = np.concatenate([xb.numpy()[:, 0] for xb, _ in it])
        assert flat.tolist() == list(range(37))

    def test_multiple_epochs(self):
        dl = DataLoader(_DS(), batch_size=10, num_workers=3)
        assert sum(1 for _ in dl) == 4
        assert sum(1 for _ in dl) == 4

    def test_worker_exception_propagates(self):
        dl = DataLoader(_Boom(), batch_size=4, num_workers=2)
        with pytest.raises(RuntimeError, match="boom at 5"):
            for _ in dl:
                pass

    def test_tensor_dataset_stays_on_threads(self):
        import jax.numpy as jnp

        from paddle_tpu.core.tensor import Tensor
        ds = TensorDataset([Tensor(jnp.arange(12.).reshape(6, 2)),
                            Tensor(jnp.arange(6))])
        dl = DataLoader(ds, batch_size=2, num_workers=2)
        from paddle_tpu.io.shm_channel import MultiprocessDataLoaderIter
        assert not isinstance(iter(dl), MultiprocessDataLoaderIter)
        assert sum(1 for _ in dl) == 3

    def test_worker_init_fn_runs(self, tmp_path):
        marks = tmp_path / "marks"
        marks.mkdir()

        # module-level-free init fn must still work under fork
        def init(worker_id, _d=str(marks)):
            open(f"{_d}/w{worker_id}", "w").close()

        dl = DataLoader(_DS(), batch_size=8, num_workers=2,
                        worker_init_fn=init)
        for _ in dl:
            pass
        assert len(list(marks.iterdir())) == 2


@needs_native
class TestReviewRegressions:
    def test_empty_record_distinct_from_timeout(self):
        from paddle_tpu.io.shm_channel import ShmRing
        ring = ShmRing("/pt_test_ring_d", slot_size=32, n_slots=2,
                       create=True)
        ring.write(b"")
        assert ring.read(timeout_us=100_000) == b""  # empty != timeout
        assert ring.read(timeout_us=10_000) is None
        ring.close()

    def test_oversize_batch_reports_real_error(self):
        class Big(Dataset):
            def __len__(self):
                return 2

            def __getitem__(self, i):
                return np.zeros(6 << 20, np.uint8)  # > 4MB slot

        dl = DataLoader(Big(), batch_size=1, num_workers=1)
        with pytest.raises(RuntimeError, match="slot_size"):
            for _ in dl:
                pass

    def test_subset_of_tensor_dataset_stays_on_threads(self):
        import jax.numpy as jnp

        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.io import Subset
        from paddle_tpu.io.shm_channel import MultiprocessDataLoaderIter
        ds = Subset(TensorDataset([Tensor(jnp.arange(8.).reshape(4, 2))]),
                    [0, 2])
        dl = DataLoader(ds, batch_size=1, num_workers=2)
        assert not isinstance(iter(dl), MultiprocessDataLoaderIter)

    def test_device_array_sample_probed(self):
        import jax.numpy as jnp

        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.io.shm_channel import MultiprocessDataLoaderIter

        class DeviceDS(Dataset):
            def __len__(self):
                return 4

            def __getitem__(self, i):
                return Tensor(jnp.zeros(3))

        dl = DataLoader(DeviceDS(), batch_size=2, num_workers=2)
        assert not isinstance(iter(dl), MultiprocessDataLoaderIter)


@needs_native
class TestPersistentWorkers:
    def test_epochs_consistent_and_processes_reused(self, tmp_path):
        marks = tmp_path / "marks"
        marks.mkdir()

        def init(worker_id, _d=str(marks)):
            import os as _os
            open(f"{_d}/w{worker_id}_{_os.getpid()}", "w").close()

        dl = DataLoader(_DS(), batch_size=8, num_workers=2,
                        worker_init_fn=init, persistent_workers=True)
        e1 = [xb.numpy().copy() for xb, _ in dl]
        e2 = [xb.numpy().copy() for xb, _ in dl]
        assert len(e1) == len(e2) and all(
            (a == b).all() for a, b in zip(e1, e2))
        # init ran once per worker process — not once per epoch. A
        # worker still mid-spawn on a loaded machine writes its mark
        # after both epochs are through (the other served them): wait
        deadline = time.time() + 20.0
        while len(list(marks.iterdir())) < 2 and time.time() < deadline:
            time.sleep(0.05)
        assert sorted(p.name.split("_")[0] for p in marks.iterdir()) \
            == ["w0", "w1"]

    def test_mid_epoch_abort_then_full_epoch(self):
        dl = DataLoader(_DS(), batch_size=8, num_workers=2,
                        persistent_workers=True)
        full = [xb.numpy().copy() for xb, _ in dl]
        it = iter(dl)
        next(it)  # abort after one batch
        again = [xb.numpy().copy() for xb, _ in dl]
        assert len(again) == len(full)
        assert all((a == b).all() for a, b in zip(full, again))

    def test_error_shutdown_invalidates_cache_and_recovers(self):
        class FlakyOnce(Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                import os as _os
                flag = "/tmp/pt_flaky_once_flag"
                if i == 3 and not _os.path.exists(flag):
                    open(flag, "w").close()
                    raise ValueError("transient")
                return np.full((2,), i, np.float32)

        import os as _os
        try:
            _os.unlink("/tmp/pt_flaky_once_flag")
        except FileNotFoundError:
            pass
        dl = DataLoader(FlakyOnce(), batch_size=4, num_workers=2,
                        persistent_workers=True)
        with pytest.raises(RuntimeError, match="transient"):
            for _ in dl:
                pass
        assert dl._persistent_iter is None  # dead iter not cached
        # a fresh epoch rebuilds workers and succeeds
        assert sum(1 for _ in dl) == 2
        _os.unlink("/tmp/pt_flaky_once_flag")
