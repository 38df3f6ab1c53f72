"""Traffic generators; each cell's traffic file names one."""
