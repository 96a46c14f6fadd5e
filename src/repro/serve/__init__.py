from .lm import ServeEngine
from .rotations import BucketKey, RotationService
from .stream import (Backpressure, DeadlineExceeded, EngineClosed,
                     StreamEngine, StreamTicket)

__all__ = ["RotationService", "BucketKey", "StreamEngine", "StreamTicket", "Backpressure",
           "DeadlineExceeded", "EngineClosed", "ServeEngine"]
