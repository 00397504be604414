"""Mean wait of the training thread for its next batch (`net.last_etl_ms`,
read by the benchmark's listener at each step of the window)."""


def read(run):
    etl = run.window.etl_ms
    return sum(etl) / len(etl) if etl else None
