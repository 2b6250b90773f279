"""Traffic generators (one module per ``generator`` a traffic file names)
and the open-loop driver that replays their requests against the
scheduler."""
