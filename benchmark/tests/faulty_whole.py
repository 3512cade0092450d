"""``faulty.py``'s run with a publish's WHOLE answer gone now and then:
where every row of a publish is a member of one share group, a row more
or less changes nothing a socket shows — the group picks among the rest —
and the fault that loses a delivery is the one that loses them all.
Started by ``test_share.py``; a benchmark run never imports it."""

import sys

from benchmark import run
from benchmark.tests.faulty import RowDropper


class AnswerDropper(RowDropper):
    @staticmethod
    def kept(rows):
        return []


if __name__ == "__main__":
    sys.exit(run.main(system_factory=AnswerDropper))
