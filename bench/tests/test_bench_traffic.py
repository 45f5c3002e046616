"""The traffic generator and the seeded catalog, against Table 2's counts."""
from collections import Counter

import numpy as np
import pytest

from bench.lib import layout
from bench.lib.catalog import Catalog, fid_of, oid_of
from bench.lib.traffic import Traffic

SEED = 2**31 + 1234


def _params(segment, fresh=True, arrivals=None):
    return {"segment": segment, "fresh_files": fresh, "warmup_stacks": 0,
            "arrivals": arrivals or {"kind": "backlog", "low_water": 8,
                                     "chunk": 8}}


@pytest.mark.parametrize("stacks,files,counts", [
    (2369, 79, {30: 78, 29: 1}),      # Table 2 locality 30, scale 0.1
    (108, 78, {2: 30, 1: 48}),        # Table 2 locality 1.38, scale 0.0007
])
def test_segment_reads_every_file_as_table2_says(stacks, files, counts):
    seg = Traffic(_params({"stacks": stacks, "files": files}), SEED).segment(0)
    assert len(seg.files) == files and len(seg.reads) == stacks
    per_file = Counter(seg.reads)
    assert set(per_file) == set(seg.files)
    assert Counter(per_file.values()) == counts


def test_fresh_segments_read_new_files_and_a_fixed_catalog_does_not():
    fresh = Traffic(_params({"stacks": 108, "files": 78}), SEED)
    a, b = fresh.segment(0), fresh.segment(1)
    assert not set(a.files) & set(b.files)
    fixed = Traffic(_params({"stacks": 108, "files": 78}, fresh=False), SEED)
    assert fixed.segment(0).files == fixed.segment(3).files
    assert fixed.segment(0).reads != fixed.segment(3).reads


def test_a_seed_fixes_the_stream_and_another_seed_reorders_the_same_work():
    p = _params({"stacks": 2369, "files": 79})
    one, again, other = (Traffic(p, s).segment(2) for s in (SEED, SEED, SEED + 1))
    assert one == again
    assert one.reads != other.reads
    assert sorted(Counter(one.reads).values()) == \
        sorted(Counter(other.reads).values())


def test_stream_marks_each_files_first_read_in_its_segment():
    t = Traffic(_params({"stacks": 108, "files": 78}), SEED)
    it = t.stream()
    seen, firsts = set(), 0
    for _ in range(108):
        fid, first = next(it)
        assert first == (fid not in seen)
        seen.add(fid)
        firsts += first
    assert firsts == 78
    assert t.ideal_hit_ratio() == pytest.approx(1 - 78 / 108)


def test_poisson_schedule_fills_the_window_with_one_set_of_gaps():
    p = _params({"stacks": 2369, "files": 79},
                arrivals={"kind": "poisson", "rate_per_s": 470.0,
                          "gap_seed": 12})
    a = Traffic(p, SEED).arrival_offsets(10.0)
    b = Traffic(p, SEED + 7).arrival_offsets(10.0)
    assert len(a) == len(b) == 4700
    assert a[0] == 0.0 and np.all(np.diff(a) > 0) and a[-1] < 10.0
    ga, gb = np.diff(a), np.diff(b)
    assert not np.array_equal(ga, gb)
    # the same multiset of gaps, in another order: the window's last gap
    # closes each schedule
    def gaps(t):
        return np.sort(np.concatenate([np.diff(t), [10.0 - t[-1]]]))
    assert np.allclose(gaps(a), gaps(b))


def test_unknown_arrival_kind_is_refused():
    with pytest.raises(ValueError, match="unknown arrival kind"):
        Traffic(_params({"stacks": 2, "files": 1},
                        arrivals={"kind": "sine"}), SEED)


@pytest.mark.parametrize("path", sorted((layout.BENCH / "traffic").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_traffic_file_generates(path):
    t = Traffic(layout.traffic(path.stem), SEED)
    seg = t.segment(0)
    assert len(seg.reads) == t.stacks and t.warmup_stacks >= 0


def test_catalog_files_are_seeded_distinct_read_only_arrays():
    cat = Catalog(SEED, 150, (100, 100), 4)
    assert cat.file_bytes == 6_000_000 and cat.distinct_files == 4
    files = [cat.file(i) for i in range(4)]
    assert files[0].shape == (150, 100, 100) and files[0].dtype == np.float32
    assert not files[0].flags.writeable
    # every file of a segment (consecutive ids) is memory of its own
    for i in range(4):
        for j in range(i + 1, 4):
            assert not np.shares_memory(files[i], files[j])
            assert not np.array_equal(files[i], files[j])
    assert np.shares_memory(cat.file(6), files[2])
    assert len({cat.offset(i) for i in range(20_000)}) == 20_000
    assert np.array_equal(Catalog(SEED, 150, (100, 100), 4).file(3),
                          cat.file(3))
    assert not np.array_equal(Catalog(SEED + 1, 150, (100, 100), 4).file(3),
                              cat.file(3))
    assert fid_of(oid_of(123456)) == 123456
