from sparkrdma_tpu.transport.completion import CompletionListener, FnListener
from sparkrdma_tpu.transport.channel import TpuChannel, ChannelError
from sparkrdma_tpu.transport.node import TpuNode


def create_node(conf, host, is_executor, executor_id, recv_listener=None,
                peer_lost_listener=None):
    """Node factory honoring ``tpu.shuffle.transport`` (python | native).

    ``native`` resolves to the C++ epoll data plane; where it was asked
    for and cannot be built, this raises with g++'s stderr (``auto``
    already resolved to python when the build fails)."""
    if conf.transport == "native":
        from sparkrdma_tpu.native.transport_lib import available, build_error

        if not available():
            raise RuntimeError(
                f"tpu.shuffle.transport=native is unavailable:\n{build_error()}"
            )
        from sparkrdma_tpu.transport.native_node import NativeTpuNode

        return NativeTpuNode(
            conf, host, is_executor, executor_id,
            recv_listener=recv_listener,
            peer_lost_listener=peer_lost_listener,
        )
    return TpuNode(
        conf, host, is_executor, executor_id,
        recv_listener=recv_listener,
        peer_lost_listener=peer_lost_listener,
    )


def mapped_delivery_enabled(conf, channel) -> bool:
    """True when a fetch should use mapped (zero-copy) delivery: the
    conf allows it and the channel's plane implements it (native
    transport only). Single definition so the record-plane fetcher and
    the device-block fetcher cannot drift."""
    return conf.mapped_fetch and hasattr(channel, "read_mapped_in_queue")


__all__ = [
    "CompletionListener",
    "FnListener",
    "TpuChannel",
    "ChannelError",
    "TpuNode",
    "create_node",
    "mapped_delivery_enabled",
]
