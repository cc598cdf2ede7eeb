"""Data pipeline (inferd_tpu.data) and training CLI (tools/train.py):
windowed sampling determinism, mesh-parallel CLI runs on the virtual
device mesh, and checkpoint save/resume through the CLI surface."""

import json

import numpy as np
import pytest

from inferd_tpu import data as datalib
from inferd_tpu.tools.train import main as train_main, parse_train_mesh


def test_dataset_windows_and_determinism(tmp_path):
    toks = np.arange(1000, dtype=np.uint16)
    path = tmp_path / "toks.npy"
    np.save(path, toks)
    ds = datalib.TokenDataset(str(path), seq_len=8)  # mmap path
    a1, t1 = ds.sample(np.random.RandomState(3), mb=2, batch=3)
    a2, t2 = ds.sample(np.random.RandomState(3), mb=2, batch=3)
    assert a1.shape == t1.shape == (2, 3, 8)
    assert a1.dtype == np.int32
    np.testing.assert_array_equal(a1, a2)  # same seed -> same batch
    # target is the next-token shift of the input window
    np.testing.assert_array_equal(t1, a1 + 1)


def test_dataset_minimum_corpus_and_last_offset():
    """The smallest accepted corpus (seq_len+1) must sample, and the final
    token must be reachable as a target (offset len-s-1 drawn)."""
    ds = datalib.TokenDataset(np.arange(9, dtype=np.int32), seq_len=8)
    a, t = ds.sample(np.random.RandomState(0), mb=1, batch=1)
    np.testing.assert_array_equal(a[0, 0], np.arange(8))
    assert t[0, 0, -1] == 8
    ds2 = datalib.TokenDataset(np.arange(12, dtype=np.int32), seq_len=8)
    seen = {
        int(ds2.sample(np.random.RandomState(i), 1, 1)[0][0, 0, 0])
        for i in range(64)
    }
    assert 3 in seen  # the last valid offset (len - s - 1) is reachable


def test_dataset_skip_matches_uninterrupted_stream():
    """batches(skip=N) yields the same tail an uninterrupted stream from the
    same seed would — the crash-equivalent reproducibility contract a
    --resume'd training run relies on."""
    ds = datalib.TokenDataset(np.arange(500, dtype=np.int32), seq_len=8)
    full = list(ds.batches(mb=2, batch=3, steps=5, seed=7))
    tail = list(ds.batches(mb=2, batch=3, steps=3, seed=7, skip=2))
    for (a1, t1), (a2, t2) in zip(full[2:], tail):
        np.testing.assert_array_equal(a1, a2)
        np.testing.assert_array_equal(t1, t2)


def test_dataset_validation():
    with pytest.raises(ValueError, match="1-D"):
        datalib.TokenDataset(np.zeros((4, 4), np.int32), seq_len=2)
    with pytest.raises(ValueError, match="at least"):
        datalib.TokenDataset(np.zeros(4, np.int32), seq_len=8)
    with pytest.raises(ValueError, match="integer"):
        datalib.TokenDataset(np.zeros(64, np.float32), seq_len=8)


def test_parse_train_mesh():
    p = parse_train_mesh("dp=2,pp=2,tp=2")
    assert (p.dp, p.pp, p.tp) == (2, 2, 2) and p.num_devices == 8
    assert parse_train_mesh("").num_devices == 1
    with pytest.raises(ValueError):
        parse_train_mesh("zz=2")


def test_train_cli_synthetic_mesh(capsys):
    """End-to-end CLI run on a dp=2,pp=2 mesh: loss finite, JSON summary."""
    rc = train_main([
        "--model", "tiny", "--random-init", "--synthetic",
        "--steps", "3", "--mb", "2", "--batch", "2", "--seq", "16",
        "--mesh", "dp=2,pp=2", "--optimizer", "adam",
        "--log-every", "0", "--device", "cpu",
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["steps"] == 3
    assert np.isfinite(out["final_loss"])


def test_train_cli_resume(tmp_path, capsys):
    """--resume continues from the snapshot: a 2+2 run's final state equals
    the step counter having advanced past the restore point."""
    ck = str(tmp_path / "ck")
    common = [
        "--model", "tiny", "--random-init", "--synthetic",
        "--mb", "1", "--batch", "2", "--seq", "16",
        "--optimizer", "adam", "--checkpoint-dir", ck,
        "--save-every", "2", "--log-every", "0", "--device", "cpu",
    ]
    assert train_main(common + ["--steps", "2"]) == 0
    capsys.readouterr()
    assert train_main(common + ["--steps", "4", "--resume"]) == 0
    err = capsys.readouterr()
    from inferd_tpu.parallel import checkpoint as ckptlib

    assert ckptlib.latest_step(ck) == 4
    assert "resumed from step 2" in err.err
