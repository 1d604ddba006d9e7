"""Of the index-key pages a sparse layer's index walk fetched, those that went
as part of a RUN, in percent: ``report()``'s ``index_pages_in_runs`` /
``index_pages_walked`` differenced over the window. An index page is 4 KB (16
rows of 128 bf16 lanes), the pool hands out and takes back runs of eight, and
the walk (``flash_attention.paged_index_walk``) takes the groups of a slot's
live entries that lead a block of its table and name adjacent pages with ONE
32 KB DMA each; a block's pages from its first group that is no run, and the
live pages past a slot's last whole group, go a DMA a page, which is the speed
of the XLA page gather the walk replaced (PERF.md §6 "PR 51"). None where the
program has no such counter, or scores its index keys through the page gather
(nothing is walked)."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    if "index_pages_in_runs" not in r0 or "index_pages_in_runs" not in r1:
        return None
    walked = (r1.get("index_pages_walked", 0)
              - r0.get("index_pages_walked", 0))
    if not walked:
        return None
    return 100.0 * (r1["index_pages_in_runs"]
                    - r0["index_pages_in_runs"]) / walked
