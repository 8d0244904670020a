"""The program's own spans of a run's window (``repro.obs``), read by the
staging metrics.

The window's products are the last ``run.products`` ``repro.product`` spans
the program recorded in this process: set-up's products come before them,
and the check after the window calls no product.  Every reader here returns
None where the program records no spans (it has no ``repro.obs``), where
fewer product spans are kept than the window has products, and where the
window has none.
"""

from __future__ import annotations

import statistics

from chipbench import xplane


def window(run):
    """(the window's product spans, {product id: their child spans}), or
    None where the program's records cannot give them."""
    try:
        from repro import obs
    except ImportError:
        return None
    if not run.products:
        return None
    spans = obs.records()
    roots = [s for s in spans if s.name == obs.PRODUCT]
    if len(roots) < run.products:
        return None
    roots = roots[-run.products:]
    children = {r.product: [] for r in roots}
    for s in spans:
        if s.product in children and s.name != obs.PRODUCT:
            children[s.product].append(s)
    return roots, children


def _covered_ns(spans) -> int:
    """The time the spans cover, overlaps once: JAX's trace events nest."""
    return sum(b - a for a, b in xplane._union(
        (s.start_ns, s.end_ns) for s in spans))


def median_ms(run, name: str):
    """Median over the window's products of the time their spans ``name``
    cover (0 for a product without one), in ms."""
    found = window(run)
    if found is None:
        return None
    roots, children = found
    return statistics.median(
        _covered_ns([s for s in children[r.product] if s.name == name]) * 1e-6
        for r in roots)


def per_product(run, counter: str):
    """The window's total of a counter its products moved, per product."""
    found = window(run)
    if found is None:
        return None
    roots, _ = found
    return sum(r.counts.get(counter, 0) for r in roots) / len(roots)
