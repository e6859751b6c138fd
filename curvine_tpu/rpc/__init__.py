from curvine_tpu.rpc.codes import RpcCode
from curvine_tpu.rpc.frame import Flags, Message
from curvine_tpu.rpc.client import Connection, ConnectionPool, RetryPolicy
from curvine_tpu.rpc.server import RpcServer, ServerConn
from curvine_tpu.rpc.transport import BulkDecoder, CoalescedWriter

__all__ = [
    "RpcCode", "Flags", "Message", "Connection", "ConnectionPool",
    "RetryPolicy", "RpcServer", "ServerConn",
    "BulkDecoder", "CoalescedWriter",
]
