"""pack_s (s): host time of ``build_engine`` (quantize, plan and pack the
weights through ``api.pack_tree``, allocate the cache), ended with
``block_until_ready`` on the packed tree."""


def read(run):
    return run.pack_s
