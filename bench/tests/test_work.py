"""Operation and byte counts of the GCN, at the benchmark's shapes."""
import pytest

from bench import work

NELL = dict(n=65755, nnz=266144 + 65755, dims=(5414, 16, 210))
PUBMED = dict(n=19717, nnz=88651 + 19717, dims=(500, 16, 3))


def test_train_step_flops_at_nell():
    n, nnz = NELL["n"], NELL["nnz"]
    fwd0 = 2 * n * 5414 * 16 + 2 * nnz * 16
    fwd1 = 2 * n * 16 * 210 + 2 * nnz * 16
    want = fwd0 + (2 * n * 5414 * 16 + 2 * nnz * 16) + fwd1 + 2 * fwd1
    assert work.gcn_train_step_flops(n, nnz, NELL["dims"]) == want
    assert work.gcn_train_step_flops(n, nnz, NELL["dims"]) == pytest.approx(2.416e10, rel=1e-3)


def test_train_step_flops_at_pubmed():
    got = work.gcn_train_step_flops(PUBMED["n"], PUBMED["nnz"], PUBMED["dims"])
    assert got == pytest.approx(6.455e8, rel=1e-3)


def test_forward_work_at_pubmed_is_bound_by_bytes():
    w = work.gcn_forward(PUBMED["n"], PUBMED["nnz"], PUBMED["dims"])
    n, nnz = PUBMED["n"], PUBMED["nnz"]
    layer0 = 4 * (n * 500 + 500 * 16 + 16 + n * 16) + 8 * nnz + 4 * (n + 1)
    layer1 = 4 * (n * 16 + 16 * 3 + 3 + n * 3) + 8 * nnz + 4 * (n + 1)
    assert w.bytes == layer0 + layer1
    assert w.flops == 2 * n * 500 * 16 + 2 * nnz * 16 + 2 * n * 16 * 3 + 2 * nnz * 3
    peak = work.peaks("TPU v5 lite")
    assert work.least_seconds(w, peak) == w.bytes / 819e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")
