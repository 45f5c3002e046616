"""The traffic generator and the seeded catalog, against Table 2's counts,
with stacks of one file and of several."""
import hashlib
import itertools
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


#: files a stack reads
KS = (1, 3, 8)


def _group(fid, seg, k):
    """The k consecutive file ids of ``seg`` that hold ``fid``, the last
    group wrapping to the segment's first ids."""
    f, base = len(seg.files), seg.files[0]
    start = (fid - base) - (fid - base) % k
    return {base + (start + i) % f for i in range(k)}


def _primaries(seg):
    return tuple(s[0] for s in seg.stacks)


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("stacks,files,counts", [
    (2369, 79, {30: 78, 29: 1}),      # Table 2 locality 30, scale 0.1
    (108, 78, {2: 30, 1: 48}),        # Table 2 locality 1.38, scale 0.0007
])
def test_segment_reads_every_file_as_table2_says(stacks, files, counts, k):
    seg = Traffic(_params({"stacks": stacks, "files": files}), SEED,
                  k).segment(0)
    assert len(seg.files) == files and len(seg.stacks) == stacks
    per_file = Counter(_primaries(seg))
    assert set(per_file) == set(seg.files)
    assert Counter(per_file.values()) == counts
    # the primaries are those of one file a stack, whatever k
    assert _primaries(seg) == _primaries(Traffic(
        _params({"stacks": stacks, "files": files}), SEED).segment(0))
    for fids in seg.stacks:
        assert len(fids) == len(set(fids)) == k
        assert set(fids) == _group(fids[0], seg, k)


@pytest.mark.parametrize("k", KS)
def test_fresh_segments_read_new_files_and_a_fixed_catalog_does_not(k):
    fresh = Traffic(_params({"stacks": 108, "files": 78}), SEED, k)
    a, b = fresh.segment(0), fresh.segment(1)
    assert not set(a.files) & set(b.files)
    assert not {f for s in a.stacks for f in s} & set(b.files)
    fixed = Traffic(_params({"stacks": 108, "files": 78}, fresh=False),
                    SEED, k)
    assert fixed.segment(0).files == fixed.segment(3).files
    assert _primaries(fixed.segment(0)) != _primaries(fixed.segment(3))
    assert {f for s in fixed.segment(3).stacks for f in s} <= \
        set(fixed.segment(0).files)


@pytest.mark.parametrize("k", KS)
def test_a_seed_fixes_the_stream_and_another_seed_reorders_the_same_work(k):
    p = _params({"stacks": 2369, "files": 79})
    one, again, other = (Traffic(p, s, k).segment(2)
                         for s in (SEED, SEED, SEED + 1))
    assert one == again
    assert _primaries(one) != _primaries(other)
    assert sorted(Counter(_primaries(one)).values()) == \
        sorted(Counter(_primaries(other)).values())
    assert one.stacks != other.stacks


@pytest.mark.parametrize("k", KS)
def test_stream_marks_each_files_first_read_in_its_segment(k):
    t = Traffic(_params({"stacks": 108, "files": 78}), SEED, k)
    it = t.stream()
    seen, firsts = set(), 0
    for _ in range(108):
        fids, first = next(it)
        assert len(fids) == k
        assert first == tuple(f for f in fids if f not in seen)
        seen.update(fids)
        firsts += len(first)
    assert firsts == 78
    assert next(it)[1] == next(iter(t.segment(1).stacks))
    assert t.ideal_hit_ratio() == pytest.approx(1 - 78 / (108 * k))


#: SHA-256 of the offered stream of segments 0-3 of each one-file cell's
#: mix, taken with the generator of one file per stack, before stacks of
#: several files were offered: each stack's file id, marked "*" where it is
#: the file's first read in its segment
ONE_FILE_STREAMS = {
    ("l30-backlog", 0): "3216e5451f7372a6f05d62e5e0d6c5d9e3b9a35010eccef12660dbc6ba279362",
    ("l30-backlog", 1): "e251580ff1b3ba493dde3be61a828ad6abd3696e96e927b48886bdcb475a0613",
    ("l30-backlog", 2): "dd1912fcaf8c50b43c05079666c73ed3b8c72829627bc88f1130cd14eefd5b63",
    ("l1.38-backlog", 0): "0fd3e0b2d1157fae44b697108d379e750ce374987ea0e7f6e7778456a4105e74",
    ("l1.38-backlog", 1): "17efaaf53a9a44b9cba95d8761bc93363344b716dc09225379d0b49a20195b61",
    ("l1.38-backlog", 2): "d5415d943a6c5ba4fbf86aed14e072c2a92edb2ea7b2871863aaa2f5c27228bd",
}


@pytest.mark.parametrize("mix,seed", sorted(ONE_FILE_STREAMS))
def test_one_file_cells_offer_the_stream_they_always_offered(mix, seed):
    b = layout.load_benchmark()
    cell = next(w for w in b["workloads"] if w["traffic"] == mix)
    k = layout.config(b, cell["config"])["files_per_stack"]
    t = Traffic(layout.traffic(mix), seed, k)
    parts = []
    for fids, first in itertools.islice(t.stream(), 4 * t.stacks):
        (fid,) = fids
        parts.append(f"{fid}{'*' if first else ''}")
    digest = hashlib.sha256(",".join(parts).encode()).hexdigest()
    assert digest == ONE_FILE_STREAMS[mix, seed]


@pytest.mark.parametrize("k", [0, 9])
def test_stacks_wider_than_a_segment_are_refused(k):
    with pytest.raises(ValueError, match=f"files_per_stack {k}"):
        Traffic(_params({"stacks": 16, "files": 8}), SEED, k)


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
    assert len(seg.stacks) == t.stacks and t.warmup_stacks >= 0


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
