"""The pages a full-attention layer's decode read fetched over the page-table
entries of the slots it served, in percent: ``report()``'s
``attend_pages_walked`` / ``attend_pages_spanned`` differenced over the
window. The page walk (``flash_attention.paged_decode_walk``) fetches a slot's
live pages, one for an idle slot; a page gather reads every entry, so 100
less this is what the walk leaves in the pool. None where the program has no
such counters, or built its step on the page gather (both stay 0)."""


def read(record: dict):
    r0, r1 = record["report0"], record["report1"]
    spanned = (r1.get("attend_pages_spanned", 0)
               - r0.get("attend_pages_spanned", 0))
    if not spanned:
        return None
    return 100.0 * (r1["attend_pages_walked"]
                    - r0["attend_pages_walked"]) / spanned
