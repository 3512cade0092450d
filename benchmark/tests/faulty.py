"""A run with the timed path broken underneath: ``benchmark.run`` with a
``DeviceBroker`` whose device path loses a matched row now and then, at
the point where the collector's dispatch hands its answer to the router.
Started by ``test_run.py``; a benchmark run never imports it."""

import sys

from benchmark import run
from benchmark.systems import DeviceBroker


class RowDropper(DeviceBroker):
    EVERY = 5

    @staticmethod
    def kept(rows):
        """What is left of the answer at fault: its first row is gone."""
        return rows[1:]

    async def warm(self) -> None:
        await super().warm()
        view, n = self.view, [0]
        fold_batch, fold_many = view.fold_batch, view.fold_many

        def lose(rows):
            n[0] += 1
            return self.kept(rows) if rows and n[0] % self.EVERY == 0 \
                else rows

        def tap_batch(mp, topics, *a, **k):
            return [lose(list(r)) for r in fold_batch(mp, topics, *a, **k)]

        def tap_many(mp, batches, *a, **k):
            return [[lose(list(r)) for r in rows]
                    for rows in fold_many(mp, batches, *a, **k)]

        view.fold_batch, view.fold_many = tap_batch, tap_many


if __name__ == "__main__":
    sys.exit(run.main(system_factory=RowDropper))
