"""Streaming windowed-shuffle pipeline (data/sampler.py + DataLoader
hooks): exactly-once visits, seeded determinism across worker types,
block-sequential degenerate case, shuffle quality, readahead hooks,
persistent process pool, and deterministic fork-worker seeding."""

import time

import numpy as np
import pytest

from pytorch_vit_paper_replication_tpu.data import (
    DataLoader,
    PackedShardDataset,
    create_packed_dataloaders,
    pack_image_folder,
    windowed_shuffle_order,
)
from pytorch_vit_paper_replication_tpu.data.imagenet import (
    ThreadLocalRng,
    eval_center_transform,
    train_augment_transform,
)
from pytorch_vit_paper_replication_tpu.data.sampler import BlockReadahead


@pytest.fixture(scope="module")
def packed_root(synthetic_folder, tmp_path_factory):
    train_dir, _ = synthetic_folder
    root = tmp_path_factory.mktemp("packed_ws")
    # Small shards so the 18-image set spans multiple blocks/shards.
    pack_image_folder(train_dir, root, pack_size=48, images_per_shard=8)
    return root


def _stream(n, block, block_order):
    return np.concatenate([
        np.arange(b * block, min((b + 1) * block, n), dtype=np.int64)
        for b in block_order])


# --- order properties -------------------------------------------------------


@pytest.mark.parametrize("n,window,block", [
    (100, 8, 16), (1000, 64, 32), (57, 1000, 10), (5, 2, 2), (1, 1, 1),
])
def test_windowed_order_is_permutation(n, window, block):
    """Every index exactly once per epoch, for windows smaller, larger,
    and equal to the dataset."""
    order, _ = windowed_shuffle_order(n, window, block,
                                      np.random.default_rng(0))
    assert sorted(order.tolist()) == list(range(n))


def test_windowed_order_deterministic():
    a, _ = windowed_shuffle_order(500, 64, 32, np.random.default_rng(7))
    b, _ = windowed_shuffle_order(500, 64, 32, np.random.default_rng(7))
    c, _ = windowed_shuffle_order(500, 64, 32, np.random.default_rng(8))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)


def test_window_one_degenerates_to_block_sequential():
    """window=1 is the raw stream: shuffled blocks, each internally
    sequential — the pure-sequential-I/O end of the knob."""
    order, border = windowed_shuffle_order(100, 1, 16,
                                           np.random.default_rng(3))
    assert np.array_equal(order, _stream(100, 16, border))


def test_window_mixing_displacement():
    """The window demonstrably mixes: mean |emit - stream| position
    displacement >= window/4 (measures ~0.7x window empirically)."""
    n, w, bs = 20000, 2048, 512
    order, border = windowed_shuffle_order(n, w, bs,
                                           np.random.default_rng(0))
    stream = _stream(n, bs, border)
    stream_pos = np.empty(n, np.int64)
    stream_pos[stream] = np.arange(n)
    out_pos = np.empty(n, np.int64)
    out_pos[order] = np.arange(n)
    disp = np.abs(out_pos - stream_pos)
    assert disp.mean() >= w / 4
    # The property readahead relies on: nothing is emitted more than
    # `window` positions before it streams in.
    assert (stream_pos - out_pos).max() <= w


# --- loader integration -----------------------------------------------------


class _IdxDataset:
    """Labels are the index — makes visit sets directly observable."""

    classes = ["a"]

    def __init__(self, n=101):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, idx):
        return np.zeros((2, 2, 3), np.float32), idx


def test_loader_windowed_exactly_once():
    dl = DataLoader(_IdxDataset(101), 8, shuffle=True, seed=0,
                    num_workers=1, shuffle_window=16, shuffle_block=8)
    seen = sorted(int(l) for b in dl for l in b["label"])
    assert seen == list(range(101))


def test_loader_windowed_sharded_partition_with_padding():
    """Multi-host shards of the windowed order partition the epoch
    exactly (same contract as the global shuffle), including the
    pad_shards path."""
    ds = _IdxDataset(101)

    def shard(pi):
        return DataLoader(ds, 8, shuffle=True, seed=5, process_index=pi,
                          process_count=2, pad_shards=True, num_workers=1,
                          shuffle_window=16, shuffle_block=8
                          )._local_indices(0)

    idx_a, valid_a = shard(0)
    idx_b, valid_b = shard(1)
    assert len(idx_a) == len(idx_b)  # equal step counts per host
    real_a = set(int(i) for i, v in zip(idx_a, valid_a) if v)
    real_b = set(int(i) for i, v in zip(idx_b, valid_b) if v)
    # Real (non-pad) rows are disjoint and cover everything.
    assert not (real_a & real_b)
    assert real_a | real_b == set(range(101))


def test_loader_windowed_visit_multiset_matches_global(packed_root):
    """Loader equality: the windowed path serves exactly the records the
    global-shuffle path serves (same multiset of labels and of decoded
    images), just in a different order."""
    ds = PackedShardDataset(packed_root,
                            eval_center_transform(32, normalize=False))
    def epoch(dl):
        labels, sums = [], []
        for b in dl:
            labels.extend(int(l) for l in b["label"])
            sums.extend(float(x.sum()) for x in b["image"])
        return sorted(labels), sorted(sums)
    g = epoch(DataLoader(ds, 4, shuffle=True, seed=3, num_workers=2))
    w = epoch(DataLoader(ds, 4, shuffle=True, seed=3, num_workers=2,
                         shuffle_window=6, shuffle_block=4))
    assert g[0] == w[0]
    np.testing.assert_allclose(g[1], w[1])


def test_loader_windowed_bit_reproducible_thread_vs_process(packed_root):
    """Acceptance: windowed epochs are bit-reproducible under --seed for
    both worker types (deterministic transform; the order is computed in
    the parent either way)."""
    ds = PackedShardDataset(packed_root,
                            eval_center_transform(32, normalize=False))
    kw = dict(shuffle=True, seed=5, num_workers=2, shuffle_window=6,
              shuffle_block=4)
    t1 = list(DataLoader(ds, 4, **kw))
    t2 = list(DataLoader(ds, 4, **kw))
    p = DataLoader(ds, 4, worker_type="process", **kw)
    p1 = list(p)
    p.close()
    assert len(t1) == len(p1) > 0
    for a, b, c in zip(t1, t2, p1):
        np.testing.assert_array_equal(a["image"], b["image"])
        np.testing.assert_array_equal(a["image"], c["image"])
        np.testing.assert_array_equal(a["label"], c["label"])


def test_loader_windowed_mid_epoch_skip(packed_root):
    """skip_next_batches (mid-epoch resume) slices the windowed order
    exactly like the global one."""
    ds = PackedShardDataset(packed_root,
                            eval_center_transform(32, normalize=False))
    kw = dict(shuffle=True, seed=9, num_workers=1, shuffle_window=6,
              shuffle_block=4)
    full = list(DataLoader(ds, 4, **kw))
    resumed = DataLoader(ds, 4, **kw)
    resumed.skip_next_batches = 2
    got = list(resumed)
    assert len(got) == len(full) - 2
    for a, b in zip(full[2:], got):
        np.testing.assert_array_equal(a["image"], b["image"])


# --- readahead --------------------------------------------------------------


class _HookRecorder:
    """Wraps a dataset, recording willneed/evict hook calls."""

    def __init__(self, ds):
        self._ds = ds
        self.classes = ds.classes
        self.willneed = []
        self.evicted = []

    def __len__(self):
        return len(self._ds)

    def __getitem__(self, idx):
        return self._ds[idx]

    def willneed_records(self, lo, hi):
        self.willneed.append((lo, hi))
        self._ds.willneed_records(lo, hi)

    def evict_records(self, lo, hi):
        self.evicted.append((lo, hi))
        self._ds.evict_records(lo, hi)


def _poll(cond, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline and not cond():
        time.sleep(0.005)


def test_loader_readahead_hints_blocks(packed_root):
    ds = _HookRecorder(PackedShardDataset(
        packed_root, eval_center_transform(32, normalize=False)))
    dl = DataLoader(ds, 4, shuffle=True, seed=1, num_workers=2,
                    shuffle_window=6, shuffle_block=4, readahead=2,
                    evict_behind=True)
    batches = list(dl)
    assert len(batches) == 5  # 18 records / bs 4
    # Every block eventually hinted, ranges legal and block-aligned. The
    # hints are another thread's: the last may come after the last batch.
    hinted = lambda: {lo // 4 for lo, _ in list(ds.willneed)}
    _poll(lambda: hinted() == set(range(5)))
    assert hinted() == set(range(5))  # 18/4 blocks
    for lo, hi in ds.willneed + ds.evicted:
        assert 0 <= lo < hi <= 18


class _HookCounter:
    def __init__(self):
        self.will, self.evict = [], []

    def willneed_records(self, lo, hi):
        self.will.append((lo, hi))

    def evict_records(self, lo, hi):
        self.evict.append((lo, hi))


def test_block_readahead_controller_evicts_behind():
    """Controller check with a stepwise consumer: every block is hinted
    ahead of need, and drained blocks (minus the window-straggler
    margin) are evicted behind."""
    rec = _HookCounter()
    ra = BlockReadahead(rec, np.arange(8), 8, 64, depth=2, window=8,
                        evict_behind=True)
    # Initial hints before any consumption: needed(0) + depth blocks.
    _poll(lambda: len(rec.will) >= 4)
    for consumed in range(8, 65, 8):
        ra.advance(consumed)
        target = min(8, (consumed + 8) // 8 + 1 + 2)
        _poll(lambda: len(rec.will) >= target)
    _poll(lambda: len(rec.evict) >= 6)
    ra.close()
    assert len(rec.will) == 8
    # margin = window//block + 1 = 2 blocks kept resident
    assert len(rec.evict) == 6
    assert rec.evict == rec.will[:6]


def test_block_readahead_skips_resumed_prefix():
    """Mid-epoch resume: a consumer position far past the start must NOT
    page in the skipped prefix (the loader sliced those records off —
    they will never be read)."""
    rec = _HookCounter()
    ra = BlockReadahead(rec, np.arange(64), 8, 512, depth=2, window=8,
                        evict_behind=False)
    ra.advance(480)  # resume at 94%: only the tail blocks matter
    _poll(lambda: len(rec.will) >= 1, timeout=2.0)
    time.sleep(0.1)  # let any erroneous prefix walk show itself
    ra.close()
    # At most the pre-advance initial hints (4) + the live tail (~4
    # blocks): far below the 64-block full walk the old behavior did.
    assert 1 <= len(rec.will) <= 10


def test_readahead_inert_without_hooks_or_block_order():
    """Global-permutation order (no block structure) and hook-less
    datasets silently skip readahead."""
    dl = DataLoader(_IdxDataset(20), 4, shuffle=True, seed=0,
                    num_workers=1, readahead=2, shuffle_window=4)
    assert len(list(dl)) == 5  # hook-less dataset: runs fine
    dl2 = DataLoader(_IdxDataset(20), 4, shuffle=True, seed=0,
                     num_workers=1, readahead=2)  # global shuffle
    assert len(list(dl2)) == 5


# --- persistent pool + deterministic fork-worker seeding --------------------


class _PidDataset:
    classes = ["a"]

    def __len__(self):
        return 8

    def __getitem__(self, idx):
        import os

        return np.zeros((2, 2, 3), np.float32), os.getpid()


def test_process_pool_persists_across_epochs():
    """ADVICE r5 #2: one pool for the loader's lifetime — the same
    worker pids serve every epoch, and close() tears them down."""
    dl = DataLoader(_PidDataset(), 2, num_workers=1,
                    worker_type="process")
    pids1 = {int(l) for b in dl for l in b["label"]}
    pool = dl._pool
    assert pool is not None
    pids2 = {int(l) for b in dl for l in b["label"]}
    assert dl._pool is pool
    assert pids1 == pids2  # same forked workers, no epoch re-fork
    dl.close()
    assert dl._pool is None
    pids3 = {int(l) for b in dl for l in b["label"]}
    assert dl._pool is not pool  # re-forked after close
    assert pids3 != pids1
    dl.close()


def test_process_worker_augmentation_seeded_reproducible(packed_root):
    """ADVICE r5 #1 acceptance: --seed reproduces augmentation draws
    under worker_type='process' — two fresh single-worker loaders with
    the same seed yield bit-identical augmented epochs (workers seed
    from [seed, ordinal, pool_token], not os.urandom)."""
    def loader():
        ds = PackedShardDataset(packed_root, train_augment_transform(
            32, normalize=True, rng=ThreadLocalRng(7)))
        return DataLoader(ds, 4, shuffle=True, seed=7, num_workers=1,
                          worker_type="process", shuffle_window=6,
                          shuffle_block=4)

    l1, l2 = loader(), loader()
    e1, e2 = list(l1), list(l2)
    assert len(e1) == len(e2) > 0
    for a, b in zip(e1, e2):
        np.testing.assert_array_equal(a["image"], b["image"])
        np.testing.assert_array_equal(a["label"], b["label"])
    # Augmentation stays LIVE across epochs (persistent pool: the worker
    # streams continue rather than replaying epoch 1).
    e1b = list(l1)
    assert not np.array_equal(e1[0]["image"], e1b[0]["image"])
    l1.close()
    l2.close()


def test_packed_dataset_page_hooks_are_noop_safe(packed_root):
    """The fadvise/madvise hooks never change what's read — only when."""
    ds = PackedShardDataset(packed_root)
    a5 = ds[5][0].copy()
    ds.willneed_records(0, len(ds))
    ds.evict_records(0, len(ds))
    np.testing.assert_array_equal(ds[5][0], a5)
    # ranges are clamped, odd inputs tolerated
    ds.willneed_records(-3, 10 ** 6)
    ds.evict_records(17, 17)


def test_pack_shuffle_seed_decorrelates_classes(synthetic_folder,
                                                tmp_path):
    """pack_image_folder(shuffle_seed=...) writes records class-mixed
    (the deep fix for windowed shuffling over class-major packs), keeps
    labels attached to their records, and is seed-deterministic."""
    from pytorch_vit_paper_replication_tpu.data import ImageFolderDataset

    train_dir, _ = synthetic_folder
    pack_image_folder(train_dir, tmp_path / "a", pack_size=16,
                      images_per_shard=8, shuffle_seed=3)
    pack_image_folder(train_dir, tmp_path / "b", pack_size=16,
                      images_per_shard=8, shuffle_seed=3)
    pack_image_folder(train_dir, tmp_path / "plain", pack_size=16,
                      images_per_shard=8)
    a = PackedShardDataset(tmp_path / "a")
    b = PackedShardDataset(tmp_path / "b")
    plain = PackedShardDataset(tmp_path / "plain")
    ref = ImageFolderDataset(train_dir)
    # Same multiset of labels, different order than class-major, same
    # order across same-seed packs.
    assert sorted(a.labels) == sorted(plain.labels)
    assert list(a.labels) == list(b.labels)
    assert list(a.labels) != list(plain.labels)
    assert list(plain.labels) == [s[1] for s in ref.samples]
    # Records follow their labels: every shuffled record matches the
    # class-major record carrying the same position in the permutation.
    order = np.random.default_rng(
        np.random.SeedSequence([3])).permutation(len(plain))
    for j in (0, 7, 17):
        np.testing.assert_array_equal(a[j][0], plain[int(order[j])][0])
        assert a[j][1] == plain[int(order[j])][1]


# --- scale harness ----------------------------------------------------------


def test_scale_epoch_harness_smoke(tmp_path):
    """tools/scale_epoch.py end-to-end at toy scale: synthetic pack is a
    valid PackedShardDataset, and the sustained protocol publishes its
    gate fields."""
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "scale_epoch", Path(__file__).resolve().parent.parent / "tools"
        / "scale_epoch.py")
    sc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sc)

    root = sc.make_synthetic_pack(tmp_path / "pack", records=96,
                                  pack_size=16, records_per_shard=32,
                                  seed=0)
    ds = PackedShardDataset(root)
    assert len(ds) == 96 and ds[95][0].shape == (16, 16, 3)
    res = sc.run_sustained(root, image_size=16, batch_size=8,
                           shuffle_window=32, readahead=1,
                           warm_records=32, num_workers=2,
                           compare_global=True, seed=0)
    assert res["records"] == 96
    assert set(res) >= {"sustained_epoch_ok", "sustained_vs_warm",
                        "warm_images_per_sec",
                        "sustained_images_per_sec", "cold_mode",
                        "global_shuffle_cold_images_per_sec"}


def test_train_cli_windowed_smoke(packed_root, synthetic_folder,
                                  tmp_path_factory):
    """--shuffle-window/--readahead wired through train.py end-to-end."""
    from pytorch_vit_paper_replication_tpu.train import main

    train_dir, test_dir = synthetic_folder
    root = tmp_path_factory.mktemp("packed_cli_ws")
    pack_image_folder(test_dir, root / "test", pack_size=48,
                      images_per_shard=8)
    results = main([
        "--dataset", "packed",
        "--train-dir", str(packed_root),
        "--test-dir", str(root / "test"),
        "--preset", "ViT-Ti/16", "--image-size", "32",
        "--patch-size", "16", "--dtype", "float32",
        "--epochs", "1", "--batch-size", "8", "--mesh-data", "8",
        "--shuffle-window", "8", "--readahead", "1",
    ])
    assert len(results["train_loss"]) == 1
    assert np.isfinite(results["train_loss"][0])
