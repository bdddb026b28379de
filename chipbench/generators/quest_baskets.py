"""Market baskets as the Apriori paper draws them (R. Agrawal, R. Srikant,
"Fast Algorithms for Mining Association Rules", VLDB 1994, the section on
synthetic data; the IBM Quest generator): T10.I4 and its kin, by whole
columns.

`patterns(rng, gen)` draws the `patterns` potentially large itemsets over
`items` items once (the caller seeds `rng` from the configuration's
`generator.pattern_seed`: the same table for every run, as `T10I4D100K`
is one table): a pattern's size is Poisson(`pattern_size`) and
at least 1; a fraction Exponential(`correlation`), capped at 1, of its
items comes from the pattern before it and the rest are uniform, all
distinct; its weight is Exponential(1), normalised over the patterns; its
corruption level is Normal(`corruption_mean`, variance
`corruption_variance`) cut to [0, 1].

`draw(rng, n, gen, pats)` fills `n` baskets: a basket's size is
Poisson(`basket_size`) and at least 1; it takes a series of patterns drawn
by weight. Of a pattern on hand items are dropped while a uniform draw is
under its corruption level, and what is kept is a uniform subset of its
items of that size. A pattern that fits what is left of the basket's size
goes in; one that does not is put in whole half the time, and is moved to
the next basket the rest of the time, as corrupted, where it is the first
on hand; either way it closes the basket. (A call's last basket hands its
pattern to nobody: a chunk is a generator of its own.) A basket left empty
takes one uniform item. Returns the distinct (basket, item) pairs sorted by
basket and item: an item is in a basket once.

    "generator": {"kind": "quest_baskets", "items": 1000, "patterns": 2000,
                  "basket_size": 10, "pattern_size": 4, "correlation": 0.5,
                  "corruption_mean": 0.5, "corruption_variance": 0.1,
                  "id_digits": 10, "item_prefix": "I", "item_digits": 3,
                  "pattern_seed": 19940912}
"""

import numpy as np

MOST_DRAWS = 32                      # patterns tried for one basket
ITEM_BITS = 10                       # a pair's key: basket << 10 | item


def patterns(rng, gen):
    """{"items": [L, widest] int16 (a pattern's items first, then -1),
    "size": [L], "cum_weight": [L], "corruption": [L]}."""
    n_items, n_pat = int(gen["items"]), int(gen["patterns"])
    if n_items > 1 << ITEM_BITS:
        raise ValueError(f"at most {1 << ITEM_BITS} items")
    size = np.clip(rng.poisson(gen["pattern_size"], n_pat), 1, n_items)
    shared = np.minimum(rng.exponential(gen["correlation"], n_pat), 1.0)
    items = np.full((n_pat, int(size.max())), -1, np.int16)
    before = np.empty(0, np.int64)
    for p in range(n_pat):
        take = min(int(round(shared[p] * size[p])), len(before))
        kept = rng.permutation(before)[:take]
        fresh = rng.permutation(np.setdiff1d(np.arange(n_items), kept))
        before = rng.permutation(
            np.concatenate([kept, fresh[:size[p] - take]]))
        items[p, :size[p]] = before
    weight = rng.exponential(1.0, n_pat)
    corruption = np.clip(
        rng.normal(gen["corruption_mean"],
                   np.sqrt(gen["corruption_variance"]), n_pat), 0.0, 1.0)
    return {"items": items, "size": size.astype(np.int64),
            "cum_weight": np.cumsum(weight / weight.sum()),
            "corruption": corruption}


def _dropped(rng, level):
    """How many items go: draws under `level` in a row, so at least j
    with probability level**j."""
    u = 1.0 - rng.random(len(level))             # (0, 1]
    with np.errstate(divide="ignore", invalid="ignore"):
        runs = np.floor(np.log(u) / np.log(level))
    runs = np.where(level <= 0.0, 0.0, np.where(level >= 1.0, np.inf, runs))
    return np.minimum(runs, 1 << 20).astype(np.int32)


def _series(rng, want, pats):
    """Every basket's own series of patterns, a column a draw, as far as
    a basket that is handed no pattern consults it (one that is handed one
    has less room and closes no later): pattern [n, MOST_DRAWS] int16,
    kept [n, MOST_DRAWS] int8 (items left after the corruption; 127 where
    nothing was drawn) and whether one that does not fit is put in whole."""
    n, sizes = len(want), pats["size"].astype(np.int32)
    pattern = np.zeros((n, MOST_DRAWS), np.int16)
    kept = np.full((n, MOST_DRAWS), 127, np.int8)
    whole = np.zeros((n, MOST_DRAWS), bool)
    left, is_open = want.copy(), np.arange(n, dtype=np.int32)
    for nth in range(MOST_DRAWS):
        m = len(is_open)
        if not m:
            break
        p = np.minimum(np.searchsorted(pats["cum_weight"], rng.random(m)),
                       len(sizes) - 1)
        keep = np.maximum(sizes[p] - _dropped(rng, pats["corruption"][p]), 0)
        anyway = rng.random(m) < 0.5
        pattern[is_open, nth], kept[is_open, nth] = p, keep
        whole[is_open, nth] = anyway
        fits = keep <= left[is_open]
        left[is_open] -= np.where(fits | anyway, keep, 0)
        is_open = is_open[fits & (left[is_open] > 0)]
    return pattern, kept, whole


def _handed_on(want, kept, whole, whole_in):
    """(handed [n], own [n], placed [n]): the place `basket * MOST_DRAWS +
    draw` of the pattern each basket is handed by the one before it (-1:
    none), how many of its own series a basket puts in, and whether it
    puts in what it was handed. A basket's end depends on the one before
    it only through what it is handed, so all baskets are settled as if
    handed nothing, and then only those whose hand-over changed, until
    none does: a handful of rounds, each over fewer baskets."""
    n = len(want)
    total = np.cumsum(kept, axis=1, dtype=np.int16)
    flat = kept.reshape(-1)
    handed = np.full(n, -1, np.int64)
    gives = np.full(n, -1, np.int64)
    own, placed = np.zeros(n, np.int32), np.zeros(n, bool)
    todo = np.arange(n)
    while len(todo):
        got = handed[todo]
        has, size = got >= 0, want[todo]
        width = np.where(has, flat[np.maximum(got, 0)], 0).astype(np.int32)
        fits = has & (width <= size)
        anyway = has & ~fits & whole_in[todo]
        moved = has & ~fits & ~anyway
        room = np.where(has, np.where(fits, size - width, 0), size)
        sums = total[todo]
        end = np.argmax(sums >= room[:, None], axis=1)   # first to fill it
        short = sums[:, -1] < room                       # none does
        at_end = sums[np.arange(len(todo)), end]
        over = ~short & (at_end > room)                  # `end` does not fit
        put_over = over & whole[todo, end]
        count = np.where(short, MOST_DRAWS, end + (~over | put_over))
        own[todo] = np.where(room > 0, count, 0)
        placed[todo] = fits | anyway
        out = np.where(moved, got, np.where(
            (room > 0) & over & ~put_over, todo * MOST_DRAWS + end, -1))
        changed = out != gives[todo]
        gives[todo] = out
        todo = todo[changed] + 1
        todo = todo[todo < n]
        handed[todo] = gives[todo - 1]
    return handed, own, placed


def draw(rng, n, gen, pats):
    """(basket [t] int32, item [t] int16): the distinct pairs of `n`
    baskets (at most 2^21 a call), sorted by basket, then item."""
    if n > 1 << (31 - ITEM_BITS):
        raise ValueError("a call draws at most 2^21 baskets: a key is int32")
    n_items, sizes = int(gen["items"]), pats["size"].astype(np.int32)
    want = np.maximum(rng.poisson(gen["basket_size"], n), 1).astype(np.int32)
    pattern, kept, whole = _series(rng, want, pats)
    handed, own, placed = _handed_on(want, kept, whole, rng.random(n) < 0.5)
    # what goes in: (basket, pattern, items kept) of its own, then handed
    b, nth = np.nonzero(np.arange(MOST_DRAWS)[None, :] < own[:, None])
    took = np.flatnonzero(placed)
    basket = np.concatenate([b, took]).astype(np.int32)
    p = np.concatenate([pattern[b, nth],
                        pattern.reshape(-1)[handed[took]]]).astype(np.int32)
    need = np.concatenate([kept[b, nth],
                           kept.reshape(-1)[handed[took]]]).astype(np.int32)
    # a uniform subset of `need` of a pattern's items, an item a round:
    # the next one stays with probability (still needed) / (still to see)
    size, items, keys = sizes[p], pats["items"].astype(np.int32), []
    for nth in range(pats["items"].shape[1]):
        stays = rng.random(len(p)) * np.maximum(size - nth, 0) < need
        keys.append((basket[stays] << ITEM_BITS) | items[p[stays], nth])
        need -= stays
    keys = np.sort(np.concatenate(keys))
    keys = keys[np.concatenate([[True], keys[1:] != keys[:-1]])]
    empty = np.flatnonzero(np.bincount(keys >> ITEM_BITS, minlength=n) == 0)
    if len(empty):
        keys = np.sort(np.concatenate(
            [keys, ((empty << ITEM_BITS)
                    | rng.integers(0, n_items, len(empty))).astype(np.int32)]))
    return keys >> ITEM_BITS, (keys & ((1 << ITEM_BITS) - 1)).astype(np.int16)
