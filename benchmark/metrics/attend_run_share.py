"""Of the pages a full-attention layer's page walk fetched, those that went as
part of a RUN, in percent: ``report()``'s ``attend_pages_in_runs`` /
``attend_pages_walked`` differenced over the window. The cache hands out and
takes back runs of adjacent pages (as many as make a fetch of 64 KB where a
page is under 32 KB), and the walk (``flash_attention.paged_decode_walk``)
takes the groups of a slot's live entries that lead a block of its table and
name adjacent pages with ONE DMA each; a block's pages from its first group
that a fork, a shared prefix or a broken run has split, and the live pages
past a slot's last whole group, go a DMA a page. 0 where a page is a fetch by
itself (32 KB and up: a run is one page long). None where the program has no
such counter, or built its step on the page gather (nothing is walked)."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    if "attend_pages_in_runs" not in r0 or "attend_pages_in_runs" not in r1:
        return None
    walked = (r1.get("attend_pages_walked", 0)
              - r0.get("attend_pages_walked", 0))
    if not walked:
        return None
    return 100.0 * (r1["attend_pages_in_runs"]
                    - r0["attend_pages_in_runs"]) / walked
