"""The served path with one answer altered where it is produced.

Started in place of `harness/served.py` by `test_broken_path.py` only.
It patches the program's wire encoder so that every verdict that names
rule ids loses its last one (what a prefilter that misses a candidate
would produce), then runs the program's own entry point.  The harness
does not know: it has to see `correct` come out false.
"""

import runpy
import sys

from ingress_plus_tpu.serve import protocol

_encode = protocol.encode_response


def altered(req_id, attack, blocked, fail_open, score, class_ids, rule_ids):
    return _encode(req_id, attack, blocked, fail_open, score, class_ids,
                   list(rule_ids)[:-1])


protocol.encode_response = altered

if __name__ == "__main__":
    sys.argv[0] = "ingress_plus_tpu.serve"
    runpy.run_module("ingress_plus_tpu.serve", run_name="__main__",
                     alter_sys=True)
