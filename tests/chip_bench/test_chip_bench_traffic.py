"""The benchmark's traffic generator: ids drawn as the program's are."""
import numpy as np
import pytest

from repro.data.synthetic import zipf_indices


@pytest.mark.parametrize("alpha", [0.5, 1.0, 1.05, 1.6])
@pytest.mark.parametrize("rows", [1, 7, 1000, 3_294_238])
def test_zipf_ids_match_zipf_indices_draw_for_draw(alpha, rows):
    import ringgen
    want = zipf_indices(np.random.default_rng(3), rows, 4096, alpha)
    got = ringgen.zipf_ids(np.random.default_rng(3).random(4096), rows, alpha)
    np.testing.assert_array_equal(got, want)


def test_uniform_ids_cover_each_table():
    import ringgen
    rows = np.array([3, 50, 1000])[None, :]
    ids = ringgen.zipf_ids(np.random.default_rng(0).random((20000, 3)), rows, 0.0)
    assert (ids >= 0).all() and (ids < rows).all()
    assert [len(np.unique(ids[:, t])) for t in range(3)] == [3, 50, 1000]


def test_ring_batches_depend_on_seed_and_index_only(manifest):
    import ringgen
    sizes = manifest.config("wide_deep")["sizes"]
    traffic = manifest.traffic("zipf")
    short = ringgen.make_ring(2**40 + 5, sizes, traffic, n=2)
    again = ringgen.make_ring(2**40 + 5, sizes, dict(traffic, ring_batches=64), n=3)
    other = ringgen.make_ring(2**40 + 6, sizes, traffic, n=1)
    for k in ("dense", "sparse", "label"):
        np.testing.assert_array_equal(short[1][k], again[1][k])
    assert not np.array_equal(short[0]["sparse"], other[0]["sparse"])
    b = short[0]
    assert b["sparse"].shape == (512, 26, 4) and b["sparse"].dtype == np.int32
    assert (b["sparse"] < np.asarray(sizes["table_rows"])[None, :, None]).all()
    assert 0.2 < b["label"].mean() < 0.8


@pytest.mark.parametrize("bad", [{"zipf_alpha": -1.0}, {"ckpt_every": -1},
                                 {"ring_batches": 2}, {"surprise": 1}])
def test_traffic_files_are_checked(manifest, bad):
    import ringgen
    with pytest.raises(ValueError):
        ringgen.check_traffic(dict(manifest.traffic("zipf"), **bad))
