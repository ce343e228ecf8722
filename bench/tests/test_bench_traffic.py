"""The benchmark's data and traffic generators (bench/traffic.py)."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, traffic  # noqa: E402

CELLS = ("kv1k-uniform.exists", "ycsb-1k.b-zipfian", "kv1k-uniform.get")


def _fnv_reference(val: int) -> int:
    """YCSB's Utils.fnvhash64, one octet at a time with Python integers."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * 1099511628211) % 2**64
    if h >= 2**63:
        h -= 2**64
    return abs(h)


def test_fnvhash64_matches_the_octet_loop():
    vals = [0, 1, 255, 256, 999_999, 10**10, 2**40 + 12345]
    assert traffic.fnvhash64(vals).tolist() == [_fnv_reference(v)
                                                for v in vals]


def test_scrambled_zipfian_top_ranks_carry_the_analytic_share():
    """YCSB's zipfian over 10^10 items at theta 0.99: the top 32,768 ranks
    carry zeta(32768)/zeta(10^10) = 43.6 % of draws exactly, and 44.1 % by
    the generator's own closed form; the value cache of the ycsb cells
    holds about that many records."""
    keys = harness.load_cell("ycsb-1k.b-zipfian").workload["keys"]
    theta, zetan = keys["zipfian_constant"], keys["zetan"]
    ranks = traffic.zipfian_ranks(traffic.rng_for(5, 1), 1_000_000,
                                  keys["items"] + 1, theta, zetan)
    share = float(np.mean(ranks < 32768))
    exact = float(np.sum(np.arange(1, 32769, dtype=np.float64) ** -theta)
                  / zetan)
    assert exact == pytest.approx(0.436, abs=0.001)
    assert share == pytest.approx(exact, abs=0.01)
    items = keys["items"] + 1
    eta = (1 - (2 / items) ** (1 - theta)) / (1 - (1 + 0.5 ** theta) / zetan)
    closed_form = (((32768 / items) ** (1 - theta)) - 1 + eta) / eta
    assert share == pytest.approx(closed_form, abs=0.003)


def test_scrambled_zipfian_stays_inside_the_records():
    keys = harness.load_cell("ycsb-1k.b-zipfian").workload["keys"]
    rec = traffic.scrambled_zipfian(traffic.rng_for(9, 1), 200_000, 1000,
                                    keys)
    assert rec.min() >= 0 and rec.max() < 1000
    # the hottest record is FNV-64 of rank 0, scrambled over records + 1
    assert np.bincount(rec).argmax() == _fnv_reference(0) % 1001


def _small(cell_name, records=4096, outstanding=512, blocks=4):
    cell = harness.load_cell(cell_name)
    cell.config["records"] = records
    cell.workload.update(outstanding=outstanding, sequence_blocks=blocks)
    return cell


@pytest.mark.parametrize("name", CELLS)
def test_data_and_traffic_repeat_per_seed(name):
    cell = _small(name)
    seed = 2**31 + 77                  # past 32 signed bits
    a = traffic.make_dataset(cell.config, seed)
    b = traffic.make_dataset(cell.config, seed)
    assert a.keys == b.keys and a.values == b.values
    sa = traffic.make_sequence(cell.config, cell.workload, a, seed)
    sb = traffic.make_sequence(cell.config, cell.workload, b, seed)
    assert (sa.op == sb.op).all() and sa.key == sb.key
    assert sa.value == sb.value
    other = traffic.make_sequence(cell.config, cell.workload, a, seed + 1)
    assert other.key != sa.key


@pytest.mark.parametrize("name", CELLS)
def test_every_block_carries_the_same_mix(name):
    cell = _small(name)
    data = traffic.make_dataset(cell.config, 3)
    seq = traffic.make_sequence(cell.config, cell.workload, data, 3)
    size = cell.workload["outstanding"]
    for kind, share in cell.workload["mix"].items():
        per_block = (seq.op.reshape(-1, size)
                     == traffic.OPS.index(kind)).sum(axis=1)
        assert (per_block == per_block[0]).all()
        assert abs(per_block[0] - share * size) <= 1
    absent = (seq.record.reshape(-1, size) < 0).sum(axis=1)
    assert (absent == round(cell.workload["absent_share"] * size)).all()


def test_absent_keys_never_collide_with_present_ones():
    cell = _small("kv1k-uniform.exists")
    data = traffic.make_dataset(cell.config, 11)
    seq = traffic.make_sequence(cell.config, cell.workload, data, 11)
    present = set(data.keys)
    for k, r in zip(seq.key, seq.record.tolist()):
        assert (k in present) == (r >= 0)
        assert len(k) == cell.config["key_bytes"]
    # a present set that holds the first draws forces redraws
    first = traffic.absent_keys(8, 32, 11, set())
    again = traffic.absent_keys(8, 32, 11, set(first[:3]))
    assert len(again) == 8 and not set(again) & set(first[:3])


def test_ycsb_keys_are_sha256_of_the_user_names():
    import hashlib
    names = traffic.ycsb_key_names(3)
    assert names[0] == "user%d" % _fnv_reference(0)
    keys = traffic.make_keys("ycsb", 3, 32, None)
    assert keys[1] == hashlib.sha256(names[1].encode()).digest()
