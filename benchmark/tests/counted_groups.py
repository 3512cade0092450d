"""``benchmark.tests.counted`` with the counters of the consumer-groups
cell beside its own: the draws made for a ShareName already drawn for
under another filter (``fastpath.share_split_picks``) and the match
programs that held the dense pass over region 0
(``tpu_matcher.phase_dense_dispatches`` of ``phase_dispatches``). Started
by ``test_groups_cell.py``; a benchmark run never imports it."""

import sys

from benchmark import run
from benchmark.tests.counted import Counted


class CountedGroups(Counted):
    async def stop(self) -> None:
        from vernemq_tpu.models import tpu_matcher
        from vernemq_tpu.protocol import fastpath

        if self.broker is not None:
            self.note(phase="program_totals",
                      share_split_picks=fastpath.share_split_picks,
                      phase_dispatches=tpu_matcher.phase_dispatches,
                      phase_dense_dispatches=(
                          tpu_matcher.phase_dense_dispatches))
        await super().stop()


if __name__ == "__main__":
    sys.exit(run.main(system_factory=CountedGroups))
