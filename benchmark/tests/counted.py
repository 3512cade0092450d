"""``benchmark.run`` with the program's own process totals noted on
standard error as the broker stops (``"phase": "program_totals"``): the
shared subscriptions' draws (``fastpath.share_*``), the wide pass's
publishes, and the PUBLISH frames the broker received. Started by
``test_fanin_cell.py``; a benchmark run never imports it."""

import sys

from benchmark import run
from benchmark.systems import DeviceBroker

SHARE = ("share_picks", "share_wire_picks", "share_stale_picks",
         "share_offline_picks")


class Counted(DeviceBroker):
    async def stop(self) -> None:
        from vernemq_tpu.models import tpu_matcher
        from vernemq_tpu.protocol import fastpath

        if self.broker is not None:
            self.note(phase="program_totals",
                      wide_publishes=tpu_matcher.wide_publishes,
                      publishes_received=int(self.broker.metrics.value(
                          "mqtt_publish_received")),
                      **{k: getattr(fastpath, k) for k in SHARE})
        await super().stop()


if __name__ == "__main__":
    sys.exit(run.main(system_factory=Counted))
