"""CLI entry: ``python -m multiverso_tpu.models.logreg.main <config_file>``
(reference Applications/LogisticRegression/src/main.cpp:8-12)."""

from __future__ import annotations

import sys

from multiverso_tpu.models.logreg.logreg import LogReg
from multiverso_tpu.utils import compile_cache
from multiverso_tpu.utils.log import Log


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if not argv:
        Log.Error("usage: python -m multiverso_tpu.models.logreg.main "
                  "<config_file>")
        return 1
    compile_cache.enable()
    lr = LogReg(argv[0])
    lr.Train()
    if lr.config.test_file:
        lr.Test()
    lr.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
