"""Share of the training window outside the program's epoch timer (%):
host glue between epochs, the α step and the dispatch of the next table
build. The build itself runs on the device after its dispatch returns,
and the next epoch's timer waits for it, so it is not in this share (see
``train_table_build_share``). Read from the program's own epoch spans
(``Trainer.metrics["epoch_s"]``) against the window's host clock."""


def read(run):
    c = run["counters"]
    if not c.get("epoch_s") or not c.get("window_s"):
        return None
    return 100.0 * (1.0 - sum(c["epoch_s"]) / c["window_s"])
