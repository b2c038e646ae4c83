"""scan.hbm_roofline: percent of the HBM roofline that the window's scans
reached. The least time is the bytes the completed queries must read (every
column each query reads, once, at its stored width, times its rows;
``READS`` in each query's reference) over the chip's HBM bandwidth
(``bench/peaks.json``); the time taken is the device's busy time in the
traced window. Defined by the work the queries require, not by the kernel
that does it. Read only where every query of the mix scans one table."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    if any(len(reads) != 1 for reads in run.reads.values()):
        return None
    total = 0
    for r in run.completed:
        for table, cols in run.reads[r.query].items():
            total += sum(run.table_rows[table] * run.column_bytes[table][c]
                         for c in cols)
    seconds = total / run.peaks["hbm_bytes_per_s"]
    return 100.0 * seconds / run.trace["busy_s"]
