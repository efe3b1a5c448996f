"""Row groups each lookup executed (``exec.task`` spans): the groups that
zone maps and bloom sketches did not prune."""

from bench import spans


def read(run):
    return spans.count_per_query(run.spans, "lookup", "exec.task")
