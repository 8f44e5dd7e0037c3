"""decode_live_page_pct: the share of the page table that the paged decode
attention walks, from the engine registry's ``serve.attn_pages_live``
(each slot's pages from the first its window reaches to the one holding
the step's token, summed over the steps of the window) over
``serve.attn_pages_table`` (slots times table width, over the same
steps).  A program without those counters reads None."""


def read(run):
    table = run.reg.get("serve.attn_pages_table")
    live = run.reg.get("serve.attn_pages_live")
    if not table or live is None:
        return None
    return 100.0 * live / table
