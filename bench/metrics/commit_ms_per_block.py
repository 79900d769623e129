"""Validation and commit (``committer.commit_block`` on the host path,
``MeshWindowCommitter`` on the window path): the ``round.commit`` spans
of the window over its blocks. Moves ``committed_tps``."""


def read(ctx):
    return ctx.span_ms_per_block("round.commit")
