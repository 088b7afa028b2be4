"""What the flash kernels' visit lists must hold, by plain loops over a
mask spec's tile table: shared by ``test_flash_masked.py`` and
``test_flash_window.py``."""
import numpy as onp

from mxnet_tpu.kernels import flash_attention as fa
from mxnet_tpu.kernels.flash_attention import (
    CLOSES, DEAD, FIRST, OPENS, PARTIAL, WHOLE)


def brute_bands(live, nseg):
    """(first k tile, width) of the band each of ``nseg`` equal segments
    of q tiles walks: as wide as the widest hull of a segment's live k
    tiles, pulled back where it would pass the last k tile."""
    nq, nk = live.shape
    per = nq // nseg
    hulls = []
    for s in range(nseg):
        ks = [j for j in range(nk) if live[s * per:(s + 1) * per, j].any()]
        hulls.append((ks[0], ks[-1] + 1))
    width = nk if nseg == 1 else max(hi - lo for lo, hi in hulls)
    return [0 if nseg == 1 else min(lo, nk - width) for lo, _ in hulls], width


def check_forward_visits(table):
    """The forward's visits are exactly the table's live tiles, a q tile's
    k tiles one after another; the first opens the q tile's sweep (it is
    the table's FIRST), the last closes it. Returns their number."""
    live = (table & (WHOLE | PARTIAL)) != 0
    qt, kt, kind, edge = fa._visits(table)
    want = [(i, j) for i in range(table.shape[0])
            for j in range(table.shape[1]) if live[i, j]]
    assert list(zip(qt.tolist(), kt.tolist())) == want
    assert (kind == table[qt, kt]).all() and (kind != DEAD).all()
    assert (((edge & OPENS) != 0) == ((kind & FIRST) != 0)).all()
    last = [j == max(onp.nonzero(live[i])[0]) for i, j in want]
    assert (((edge & CLOSES) != 0) == onp.array(last)).all()
    assert all(a.dtype == onp.int32 for a in (qt, kt, kind, edge))
    return len(want)


def check_backward_visits(table, nseg):
    """The backward's visits in ``nseg`` segments: exactly the table's
    live tiles by segment, then k tile, then q tile of the segment, and
    one visit of kind DEAD for each k tile of a segment's band with no
    live q tile there, so that every block of dk and dv is written once;
    every q tile's rows of dq are assigned once, by its first visit.
    Returns ``(live visits by segment, zero visits by segment)``."""
    live = (table & (WHOLE | PARTIAL)) != 0
    nq, nk = table.shape
    per = nq // nseg
    first, width = brute_bands(live, nseg)
    bands = fa._segment_bands(table, nseg)
    assert (first, width) == (bands[0].tolist(), bands[1])
    qt, kt, kind, edge, slot = (a.tolist()
                                for a in fa._visits(table, bands))

    want, zeros = [], [0] * nseg
    for s in range(nseg):
        for j in range(first[s], first[s] + width):
            qs = [i for i in range(s * per, (s + 1) * per) if live[i, j]]
            want += [(s, j, i) for i in qs] or [(s, j, None)]
            zeros[s] += not qs
    assert len(want) == len(qt)
    assigned, written, open_slot = set(), [], None
    for at, (s, j, i) in enumerate(want):
        assert kt[at] == j and slot[at] == s * width + j - first[s]
        assert qt[at] // per == s       # dq's block is the segment's
        if i is None:
            assert kind[at] == DEAD and edge[at] == OPENS | CLOSES
        else:
            assert qt[at] == i and kind[at] == table[i, j] != DEAD
            # dq's rows: assigned by the q tile's first visit, then added to
            assert ((kind[at] & FIRST) != 0) == (i not in assigned)
            assigned.add(i)
        # dk, dv: a slot's visits are one run that opens and closes once
        assert ((edge[at] & OPENS) != 0) == (open_slot != slot[at])
        open_slot = slot[at]
        if edge[at] & CLOSES:
            written.append(slot[at])
            open_slot = None
        else:
            assert want[at + 1][:2] == (s, j)
    assert assigned == set(range(nq))
    assert written == list(range(nseg * width))
    lives = [sum(1 for s, _, i in want if s == n and i is not None)
             for n in range(nseg)]
    assert sum(lives) == live.sum()
    return lives, zeros


def check_visits(table):
    """Both passes' visits over ``table``, the backward in every number
    of segments up to 8 that divides its q tiles."""
    n = check_forward_visits(table)
    for nseg in (1, 2, 4, 8):
        if table.shape[0] % nseg == 0:
            lives, _ = check_backward_visits(table, nseg)
            assert sum(lives) == n
