"""Row x column squared distances the traced search's neighbour searches
computed: the sum over its launches of
``search_report["knn_distance_entries_per_launch"]`` (blocks x rows a block
x every row).  4.9e9 = 70 000 x 70 000 for ONE search of the cell's rows,
5 x that for one a fold or a candidate's.  ``None`` where the report has no
such counter (a program from before it, or a search through another
family)."""


def read(ctx):
    entries = ctx["report"].get("knn_distance_entries_per_launch")
    if not entries:
        return None
    return sum(entries)
