"""The prefix tree's eviction order (serving/cache.py `PrefixIndex`).

`evict_lru` pops its victims from a heap that stands between evictions,
kept as the tree changes. What it must free is what one walk of the tree
would: `reference_victims` below is that walk, the body `evict_lru` had
until PR 41 (one DFS + one heap), kept here as the plain reference. The
histories drive a `PagedAllocator` with no model and compare, before
EVERY eviction, the pages freed and their order with the reference's on
a twin of the tree; after every operation the sanitizer's check holds
the heap to the tree (a live entry for exactly the evictable leaves, the
heap within its bound).

The cost tests pin the complexity, not a time: an evicting admission's
work does not grow with the tree, and a history that never evicts leaves
the heap within its bound."""

import heapq
import sys

import numpy as np
import pytest

from accelerate_tpu.serving import PagedAllocator, PrefixIndex, Request
from accelerate_tpu.serving.cache import _RadixNode
from accelerate_tpu.serving.sanitizer import check_eviction_candidates
from accelerate_tpu.serving.scheduler import Slot

PS = 4      # tokens a page, everywhere below


def reference_victims(index, n, swap_out=None):
    """The pages an eviction of `n` frees, in order: every refcount-0
    effective leaf of the tree collected by ONE walk, heapified by
    `(last_used, page)`, popped oldest first; a parent joins when its
    last HBM child has gone. Mutates `index` as the eviction does (run
    it on a `twin`) and knows nothing of the standing heap."""
    if n <= 0 or index.cached_pages - index.mapped_pages < n:
        return []
    heap = []
    stack = [c for c in index.root.children.values()
             if c.residency == "hbm"]
    while stack:
        node = stack.pop()
        hbm_children = [c for c in node.children.values()
                        if c.residency == "hbm"]
        if hbm_children:
            stack.extend(hbm_children)
        elif node.refcount == 0:
            heap.append((node.last_used, node.page, node))
    heapq.heapify(heap)
    freed = []
    while len(freed) < n:
        _, _, victim = heapq.heappop(heap)
        parent = victim.parent
        freed.append(victim.page)
        index.cached_pages -= 1
        if swap_out is not None and swap_out(victim):
            victim.page = -1
            victim.residency = "host"
            index.host_pages += 1
        else:
            del parent.children[victim.key]
            victim.parent = None
            drop_stack = list(victim.children.values())
            while drop_stack:
                orphan = drop_stack.pop()
                drop_stack.extend(orphan.children.values())
                index.host_pages -= 1
        if parent is not index.root and parent.refcount == 0 \
                and parent.residency == "hbm" \
                and not any(c.residency == "hbm"
                            for c in parent.children.values()):
            heapq.heappush(heap, (parent.last_used, parent.page, parent))
    return freed


def twin(index):
    """A deep copy of the tree and its counters (not of the heap)."""
    copy = PrefixIndex(index.page_size)
    copy.cached_pages = index.cached_pages
    copy.mapped_pages = index.mapped_pages
    copy.host_pages = index.host_pages
    stack = [(index.root, copy.root)]
    while stack:
        src, dst = stack.pop()
        for key, child in src.children.items():
            node = _RadixNode(key, child.page, dst)
            node.refcount = child.refcount
            node.last_used = child.last_used
            node.residency = child.residency
            dst.children[key] = node
            stack.append((child, node))
    return copy


def shape(index):
    """The tree as nested tuples, for comparing a tree with its twin."""
    def of(node):
        return (node.page, node.refcount, node.last_used, node.residency,
                tuple(sorted((k, of(c)) for k, c in node.children.items())))
    return (index.cached_pages, index.mapped_pages, index.host_pages,
            of(index.root))


class FakeTier:
    """The host tier's two hooks, model-free: `offer` answers by `mode`
    (`accept`, `refuse`, `alternate`) and keeps the mirrors it holds."""

    def __init__(self, mode):
        self.mode = mode
        self.offers = 0
        self.mirrors = set()
        self.dropped = 0

    def offer(self, node):
        self.offers += 1
        yes = self.mode == "accept" or (self.mode == "alternate"
                                        and self.offers % 2 == 1)
        if yes:
            self.mirrors.add(id(node))
        return yes

    def discard(self, node):
        self.mirrors.remove(id(node))
        self.dropped += 1


def checked(allocator):
    """Hold every `evict_lru` of `allocator`'s index to the reference:
    the same pages in the same order, the same tree afterwards, whatever
    the tier answered. Returns the list of evictions seen."""
    index = allocator.index
    seen = []
    inner = index.evict_lru

    def evict_lru(n, swap_out=None):
        before = twin(index)
        answers = []

        def recording(node):
            answers.append(bool(swap_out(node)))
            return answers[-1]

        freed = inner(n, swap_out=None if swap_out is None else recording)
        replay = iter(answers)
        want = reference_victims(
            before, n,
            swap_out=None if swap_out is None else lambda node: next(replay))
        assert freed == want, (n, freed, want)
        assert len(freed) in (0, n)
        assert shape(index) == shape(before)
        seen.append(freed)
        return freed

    index.evict_lru = evict_lru
    return seen


def make(num_pages, mode=None):
    al = PagedAllocator(page_size=PS, num_pages=num_pages, pad_slack=0)
    tier = None
    if mode is not None:
        tier = FakeTier(mode)
        al.swap_out = tier.offer
        al.index.drop_host = tier.discard
    return al, tier


def req(tokens, mnt=0):
    return Request(prompt=np.asarray(tokens, np.int32), max_new_tokens=mnt)


def slot_of(alloc, request, prompt_done=None):
    s = Slot(0)
    s.alloc, s.request = alloc, request
    s.prompt_done = request.prompt_len if prompt_done is None else prompt_done
    return s


def serve(al, tokens, mnt=0):
    """Admit, finish and retire one request: its full pages stay cached."""
    r = req(tokens, mnt)
    alloc = al.allocate(r)
    assert alloc is not None
    al.release(slot_of(alloc, r), finished=True)
    return alloc


def pages(first, n):
    """`n` pages of tokens no other call shares (`first` names them)."""
    return list(range(first, first + n * PS))


# ---------------------------------------------------------------------------
# random histories
# ---------------------------------------------------------------------------


def run_history(seed, num_pages, mode, shared, steps, floor):
    rng = np.random.default_rng(seed)
    al, tier = make(num_pages, mode)
    al.index.LRU_FLOOR = floor      # a low floor: rebuilds all the time
    evictions = checked(al)
    biggest = max(1, num_pages // 4)
    docs = [pages(1000 * (d + 1), int(rng.integers(1, biggest + 1)))
            for d in range(6)]
    live = []
    fresh = [10 ** 6]

    def prompt():
        fresh[0] += 10 ** 4
        if shared and rng.random() < 0.8:
            doc = docs[int(rng.integers(len(docs)))]
        else:
            doc = pages(fresh[0], int(rng.integers(1, biggest + 1)))
        # the whole document or a prefix of it: branches inside a path
        doc = doc[:PS * int(rng.integers(1, len(doc) // PS + 1))]
        tail = int(rng.integers(1, 2 * PS + 2))
        return doc + list(range(fresh[0] - tail, fresh[0]))

    def admit():
        r = req(prompt(), int(rng.integers(0, 2 * PS)))
        books = (al.pool.free_count, al.index.cached_pages,
                 al.index.mapped_pages, al.index.host_pages, al.evictions)
        alloc = al.allocate(r)
        if alloc is None:
            # a failed admission: the path re-stamped, nothing else moved
            assert books == (al.pool.free_count, al.index.cached_pages,
                             al.index.mapped_pages, al.index.host_pages,
                             al.evictions)
            return None
        return alloc, r

    for _ in range(steps):
        op = rng.random()
        if op < 0.45 and len(live) < 4:
            got = admit()
            if got is not None:
                live.append(got)
                # the engine installs a swap-in's bytes and the tier lets
                # its mirror go; a rollback (below) keeps the mirror
                for node, _ in got[0].swap_ins or ():
                    tier.mirrors.remove(id(node))
        elif op < 0.55:
            got = admit()
            if got is not None:
                al.rollback(got[0])
        elif op < 0.65 and live:
            alloc, r = live[int(rng.integers(len(live)))]
            s = slot_of(alloc, r, int(rng.integers(0, r.prompt_len + 1)))
            al.publish_prompt(s)
        elif op < 0.72:
            al.index.match(np.asarray(prompt(), np.int32))
        elif live:
            alloc, r = live.pop(int(rng.integers(len(live))))
            finished = rng.random() < 0.8
            done = r.prompt_len if rng.random() < 0.8 else \
                int(rng.integers(0, r.prompt_len + 1))
            # what was published is prefilled: retire no earlier than that
            done = max(done, len(alloc.nodes) * PS)
            al.release(slot_of(alloc, r, min(done, r.prompt_len)), finished)
        check_eviction_candidates(al.index)
        assert al.pool.free_count + al.index.cached_pages + sum(
            len(a.pages) - len(a.nodes) for a, _ in live) == num_pages
    for alloc, r in live:
        al.release(slot_of(alloc, r), finished=True)
    # drain the tree through the same door: everything left is evictable
    assert al.index.mapped_pages == 0
    al.index.evict_lru(al.index.cached_pages, swap_out=al.swap_out)
    check_eviction_candidates(al.index)
    assert al.index.cached_pages == 0
    if tier is not None:
        assert len(tier.mirrors) == al.index.host_pages
    return evictions


@pytest.mark.parametrize("shared", [True, False], ids=["shared", "unshared"])
@pytest.mark.parametrize("mode", [None, "accept", "refuse", "alternate"],
                         ids=["no-tier", "accept", "refuse", "alternate"])
@pytest.mark.parametrize("num_pages", [8, 32, 128, 512])
@pytest.mark.parametrize("seed", [0, 1])
def test_random_histories_evict_what_the_walk_would(seed, num_pages, mode,
                                                    shared):
    evictions = run_history(
        seed=1000 * seed + num_pages + (7 if shared else 0)
        + 13 * ["no-tier", "accept", "refuse", "alternate"].index(
            mode or "no-tier"),
        num_pages=num_pages, mode=mode, shared=shared,
        steps=400 if num_pages <= 128 else 250,
        floor=PrefixIndex.LRU_FLOOR if seed == 0 else 2)
    # a history that never evicts proves nothing
    assert sum(1 for freed in evictions if freed) >= 3


# ---------------------------------------------------------------------------
# one case an event that makes or unmakes a candidate
# ---------------------------------------------------------------------------


def drain(al):
    """Evict all that is evictable (destructively), held to the
    reference; the pages freed."""
    seen = checked(al)
    n = al.index.cached_pages - al.index.mapped_pages
    freed = al.index.evict_lru(n)
    assert len(freed) == n and seen == [freed]
    check_eviction_candidates(al.index)
    return freed


def case_rollback_of_a_swap_in():
    """`rollback` releases the path first and only then turns its
    swap-ins back to host: the path's last HBM page becomes evictable
    with no event of its own. Here it was no candidate when it was
    matched either (it had an HBM child, which this very admission
    evicted), so nothing but the undo can enter it."""
    al, tier = make(8, "accept")
    checked(al)
    doc = pages(100, 3)
    serve(al, doc + [1])                        # A0 A1 A2 cached
    serve(al, doc[:PS] + pages(500, 1) + [1])   # X cached under A0
    cold = req(pages(900, 6))
    c = al.allocate(cold)                       # evicts A2, then A1: to host
    assert al.index.host_pages == 2 and al.index.cached_pages == 2
    al.release(slot_of(c, cold), finished=False)
    hold = req(pages(700, 4))
    assert al.allocate(hold) is not None and al.pool.free_count == 2
    a0 = al.index.root.children[np.asarray(doc[:PS], np.int32).tobytes()]
    again = al.allocate(req(doc + [1]))         # 2 swap-ins + 1 private:
    assert again is not None                    # one short, X goes to host
    assert len(again.swap_ins) == 2 and al.evictions == 3
    assert al.index.cached_pages == 3 and al.index.mapped_pages == 3
    al.rollback(again)
    assert a0.refcount == 0 and al.index.cached_pages == 1
    assert al.index.host_pages == 3
    check_eviction_candidates(al.index)
    assert drain(al) == [a0.page]               # and it takes the mirrors
    assert tier.dropped == 3 and not tier.mirrors


def case_match_then_not_acquired():
    """The engine's dedup hold calls `match` and admits nothing
    (`Engine._hold_for_dedup`): the stamps move, so must the entries."""
    al, _ = make(8)
    older, newer = pages(100, 2), pages(200, 2)
    serve(al, older + [1])
    serve(al, newer + [1])
    path = al.index.match(np.asarray(older + [1], np.int32))
    assert [n.refcount for n in path] == [0, 0]
    check_eviction_candidates(al.index)
    # `newer` goes first now, leaf before parent, though it was cached last
    seen = checked(al)
    al.index.evict_lru(2)
    assert al.index.match(np.asarray(newer + [1], np.int32)) == []
    assert len(al.index.match(np.asarray(older + [1], np.int32))) == 2
    assert len(seen[0]) == 2


def case_adopt_host_under_insert():
    """A chunk swapped out while a request prefilled its own copy of it:
    the retirement's `insert` re-homes the node at the fresh page, and
    the walk's end is evictable under its new stamp and page."""
    al, tier = make(12, "accept")
    checked(al)
    doc = pages(100, 3)
    mine = req(doc + [1])
    own = al.allocate(mine)                     # cold: a private copy
    serve(al, doc + [1])                        # the tree's copy
    cold = req(pages(900, 7))
    c = al.allocate(cold)                       # two short: A2, A1 to host
    assert al.index.host_pages == 2
    al.release(slot_of(c, cold), finished=False)
    al.release(slot_of(own, mine), finished=True)
    assert al.index.host_pages == 0 and tier.dropped == 2
    assert al.index.cached_pages == 3
    leaf = al.index.match(np.asarray(doc + [1], np.int32))[-1]
    assert leaf.page == own.pages[2]            # the adopted page
    check_eviction_candidates(al.index)
    assert drain(al)[0] == own.pages[2]


def case_parent_by_its_last_childs_eviction():
    """A document under two questions: it is no candidate while either
    question's page is cached, and the next one the moment the second
    goes, ahead of every newer page."""
    al, _ = make(16)
    doc = pages(100, 2)
    serve(al, doc + pages(300, 1) + [1])
    serve(al, doc + pages(400, 1) + [1])
    serve(al, pages(500, 2) + [1])              # a newer, unrelated path
    root_children = al.index.root.children
    d0 = root_children[np.asarray(doc[:PS], np.int32).tobytes()]
    d1 = next(iter(d0.children.values()))
    assert len(d1.children) == 2
    assert not al.index._evictable(d1) and not al.index._evictable(d0)
    first, second = (c.page for c in d1.children.values())
    # the second question's admission re-stamped the document: it is
    # newer than the first question's page, and still goes only after
    # both, ahead of the unrelated path cached last
    assert drain(al)[:4] == [first, second, d1.page, d0.page]


def case_failed_admission():
    """Too few evictable pages: `[]`, nothing evicted, not an entry of
    the heap moved; and the O(1) bail never pops."""
    al, _ = make(8)
    serve(al, pages(100, 3) + [1])
    held = al.allocate(req(pages(200, 3) + [1]))
    assert held is not None and al.pool.free_count == 1
    entries = list(al.index._lru)
    stale = al.index.lru_stale
    assert al.index.evict_lru(4) == []
    assert al.allocate(req(pages(300, 6))) is None
    assert al.index._lru == entries and al.index.lru_stale == stale
    assert al.evictions == 0 and al.index.cached_pages == 3
    assert len(drain(al)) == 3


def case_an_entry_pushed_twice():
    """Mapped and unmapped again with no new stamp, a page is offered
    under the key it already stands under: two entries equal in stamp
    and page must never fall through to comparing the nodes, and the
    page is evicted once."""
    al, _ = make(8)
    serve(al, pages(100, 2) + [1])
    serve(al, pages(200, 2) + [1])
    index = al.index
    leaf = index.root.children[
        np.asarray(pages(100, 1), np.int32).tobytes()]
    leaf = next(iter(leaf.children.values()))
    path = [leaf.parent, leaf]
    for _ in range(3):
        index.acquire(path)
        index.release(path)
    mine = [e for e in index._lru if e[3] is leaf]
    assert len(mine) == 4 and len({e[:2] for e in mine}) == 1
    check_eviction_candidates(index)
    stale = index.lru_stale
    freed = drain(al)
    assert freed.count(leaf.page) == 1 and len(freed) == 4
    assert index.lru_stale - stale == 3 and index._lru == []


def case_extend_path_not_acquired():
    """`extend_path` hands back refcount-0 nodes: until the publisher
    acquires them the last one is evictable, and stands as such."""
    al, _ = make(8)
    r = req(pages(100, 3) + [1])
    alloc = al.allocate(r)
    nodes = al.index.extend_path(r.prompt, alloc.pages, 0, 3)
    assert [n.refcount for n in nodes] == [0, 0, 0]
    check_eviction_candidates(al.index)
    assert [e[3] for e in al.index._lru] == [nodes[-1]]
    al.index.acquire(nodes)
    alloc.nodes.extend(nodes)
    check_eviction_candidates(al.index)         # stale now, and no other
    al.release(slot_of(alloc, r), finished=True)
    assert drain(al) == [n.page for n in reversed(nodes)]


CASES = [case_rollback_of_a_swap_in, case_match_then_not_acquired,
         case_adopt_host_under_insert,
         case_parent_by_its_last_childs_eviction, case_failed_admission,
         case_an_entry_pushed_twice, case_extend_path_not_acquired]


@pytest.mark.parametrize("case", CASES,
                         ids=[c.__name__[len("case_"):] for c in CASES])
def test_event(case):
    case()


# ---------------------------------------------------------------------------
# cost: the complexity, not a time
# ---------------------------------------------------------------------------


def events(run):
    """Python and C calls made by `run()`: a walk of N nodes makes some
    calls a node, whatever it is written as. The interpreter's own
    profile hook counts them, so the code under test carries no counter."""
    count = [0]

    def hook(frame, event, arg):
        if event in ("call", "c_call"):
            count[0] += 1

    previous = sys.getprofile()
    sys.setprofile(hook)
    try:
        out = run()
    finally:
        sys.setprofile(previous)
    return count[0], out


def full_pool(cached, doc_pages=16):
    """A pool of `cached` pages in retired documents of `doc_pages`, and
    the one free page that the last of them needed for its last token."""
    al, _ = make(cached + 1)
    for d in range(cached // doc_pages):
        serve(al, pages(10 ** 6 + d * 10 ** 3, doc_pages) + [1])
    assert al.index.cached_pages == cached and al.pool.free_count == 1
    return al


def test_an_evicting_admission_costs_its_pages_not_the_tree():
    work, walk = {}, {}
    for cached in (256, 2048, 16384):
        al = full_pool(cached)
        walk[cached], want = events(
            lambda: reference_victims(twin(al.index), 8))
        cold = req(pages(5, 9))
        work[cached], alloc = events(lambda: al.allocate(cold))
        assert alloc is not None and al.evictions == 8
        assert alloc.pages[:8] == want[::-1]    # the free list is a stack
    # 64 times the tree: the walk's work follows it, the admission's
    # does not (its heap is 64 times as long, 6 levels deeper: in C)
    assert walk[16384] > 30 * walk[256]
    assert work[16384] <= work[256] + 8
    assert work[2048] <= work[256] + 8
    # and it follows the pages evicted
    al = full_pool(2048)
    more, alloc = events(lambda: al.allocate(req(pages(5, 65))))
    assert alloc is not None and al.evictions == 64
    assert work[2048] < more < 8 * work[2048]


def test_a_history_that_never_evicts_keeps_the_heap_bounded():
    """10,000 admissions over four documents in a pool that never
    fills: every admission re-stamps a path and retires it, two pushes
    and no pop. The rebuild walks the heap now and then."""
    al, _ = make(64)
    docs = [pages(1000 * (d + 1), 4) for d in range(4)]
    longest = 0
    for i in range(10_000):
        serve(al, docs[i % 4] + [i])
        longest = max(longest, len(al.index._lru))
        assert len(al.index._lru) <= al.index.lru_bound()
    assert al.evictions == 0 and al.index.cached_pages == 16
    assert al.index._lru_serial > 19_000        # they were all pushed
    assert longest > 16                         # and stale ones stood
    check_eviction_candidates(al.index)
    assert len(drain(al)) == 16
