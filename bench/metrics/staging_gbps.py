"""HMM / transfer: bytes the scale event's staging moved between chips,
initialised on the target chips or streamed from the host
(``TransferStats`` ``p2p_bytes + init_bytes + expert_h2d_bytes +
h2d_bytes``, as frozen when staging completed) over the staging wall time
(``ScaleEvent.stage_wall_s``), in GB/s.  Moves ``scale_up_s``.  Scale
cells only."""


def read(run):
    s = run.scale
    if s is None or s["event"] is None or s["stage_stats"] is None:
        return None
    st, wall = s["stage_stats"], s["event"].stage_wall_s
    moved = st.p2p_bytes + st.init_bytes + st.expert_h2d_bytes + st.h2d_bytes
    if wall <= 0 or moved <= 0:
        return None
    return moved / wall / 1e9
